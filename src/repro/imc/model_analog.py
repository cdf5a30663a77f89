"""Model-level analog accuracy: whole transformer forwards through the
AFMTJ differential-conductance MVM (DESIGN.md §12).

PR 2's ``imc.analog_pipeline`` scores one decode projection at a time; the
paper's case-study claim only matters if the analog path preserves accuracy
at the *model* level.  This module routes **every linear layer** of a real
architecture forward (``models/model.py``) through the analog MVM via the
``models.common.linear`` interception hook, and measures logits KL,
token-match rate, and task perplexity against the exact f32 forward across
the (adc_bits x TMR x process corner x residual write BER) surface.

Three execution modes per linear:

  * ``fake``   — the fused fake-analog Pallas kernel
                 (``kernels.fake_analog``): programming replayed inside the
                 matmul tiles, everything traced, one compile per
                 (shape, adc_bits); sweep axes (TMR, corner, BER, seed) are
                 plain data.  This is the tractable surface path.
  * ``device`` — the full ``program_weights`` + ``analog_matmul`` chain,
                 host-synced and compile-keyed per ADC full scale; the
                 ground truth the fake path is parity-pinned against, sped
                 up by the content-keyed weight-programming cache below.
  * ``bnn``    — the paper's 1-cell/weight XNOR mode
                 (``analog_pipeline.binary_matmul``), fully traced.

The forward here is *eagerly unrolled* over layers (stacked block params
indexed per repeat) instead of ``lax.scan``: the device path reduces to
Python floats during programming, which cannot live under a scan; the fake
and bnn paths are traced end-to-end and jitted whole-forward, so the unroll
costs only compile-time linear in depth at smoke sizes.

Weight-programming cache: ``program_weights`` is content-keyed on
(weight-array hash, programming-relevant AnalogConfig axes, corner, seed,
bitline) through ``campaign.cache``'s named-array store — an ``adc_bits``
or ``full_scale_sigmas`` sweep re-programs nothing, a TMR/corner/BER sweep
re-programs only the axis that changed.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.campaign import cache as _cache
from repro.circuit.bitline import BitlineParams, cell_conductance, column_ir_drop
from repro.configs.base import ArchConfig
from repro.configs.registry import get_arch, smoke_config
from repro.core.params import PROCESS_CORNERS, VariationSpec
from repro.imc import faults as hard_faults
from repro.imc.analog_pipeline import (AnalogConfig, ProgrammedArray,
                                       _device_for, _resolved_variation,
                                       analog_matmul, binary_matmul,
                                       program_weights)
from repro.imc.faults import FaultSpec, RepairPolicy
from repro.kernels.fake_analog import (ROW_ATT_NEG, ROW_ATT_POS, ROW_DECODE,
                                       ROW_G_AP, ROW_G_FS, ROW_G_SCALE,
                                       ROW_I_MAX, ROW_R_ACCESS, AUX_ROWS,
                                       fake_analog_grouped_pallas,
                                       fake_analog_mac_pallas,
                                       pos_neg_conductance)
from repro.kernels.ops import _default_interpret
from repro.models import model as model_mod
from repro.models.common import group_ids, intercept_linears, rms_norm
from repro.runtime import telemetry

# bumped when the programming chain changes numerically — stale cache
# entries then simply never match (same policy as campaign KERNEL_VERSION)
PROGRAMMING_VERSION = 1


# ---------------------------------------------------------------------------
# fake-analog fast path (single projection)
# ---------------------------------------------------------------------------
def _round_2sig(v: jnp.ndarray) -> jnp.ndarray:
    """Traceable equivalent of the device path's ``float(f"{v:.2g}")`` ADC
    full-scale rounding (2 significant digits).  Decimal-vs-binary half-way
    ties can differ in the last digit — parity tests pass an explicit
    ``i_max`` where exactness matters."""
    e = jnp.floor(jnp.log10(v))
    p = 10.0 ** (e - 1.0)
    return jnp.round(v / p) * p


def _fake_plane(w, bl: BitlineParams, scal: Dict[str, jnp.ndarray], *,
                apply_fet: bool, use_fail: bool, ir_drop: bool,
                has_imax: bool, use_faults: bool = False,
                repair: Optional[RepairPolicy] = None):
    """Weight side of the operand preamble, one crossbar:
    ``(wn, fail, att_p, att_n, att_mean, w_scale, g_rms)`` (``g_rms`` None
    when the ADC full scale is given)."""
    w = jnp.asarray(w, jnp.float32)
    k_rows, n_cols = w.shape
    g_ap, g_fs = scal["g_ap"], scal["g_fs"]

    w_scale = jnp.max(jnp.abs(w))
    w_scale = jnp.where(w_scale == 0.0, 1.0, w_scale)
    wn = w / w_scale

    if use_fail:
        # identical draw stream to program_weights' residual write errors
        kber = jax.random.fold_in(jax.random.PRNGKey(scal["seed"]), 0x5EB)
        kb1, kb2 = jax.random.split(kber)
        fail = (jax.random.bernoulli(kb1, scal["ber"], wn.shape)
                .astype(jnp.float32)
                + 2.0 * jax.random.bernoulli(kb2, scal["ber"], wn.shape)
                .astype(jnp.float32))
    else:
        fail = jnp.zeros_like(wn)

    col_ok = None
    if use_faults:
        # hard-defect planes (DESIGN.md §13): rates + seed arrive as traced
        # scalars, so a fault-rate sweep is pure data — 0 new compiles.  The
        # repair policy IS a compile key (it restructures the trace).  Fault
        # bits are disjoint from the write-ber bits, so + is bitwise OR.
        code = hard_faults.fault_code_plane(
            k_rows, n_cols, seed=scal["f_seed"], stuck_on=scal["f_on"],
            stuck_off=scal["f_off"], dead_row=scal["f_drow"])
        col_ok = hard_faults.column_ok_plane(
            n_cols, seed=scal["f_seed"], dead_col=scal["f_dcol"])
        code, col_ok = hard_faults.apply_repair(code, col_ok, repair)
        fail = fail + code

    # column statistics (IR planes, ADC sizing) reduce over the same cell
    # conductances the kernel replays — shared helper, fused reductions
    tp, tn = pos_neg_conductance(wn, fail, g_ap, g_fs, scal["g_scale"],
                                 scal["r_access"], apply_fet=apply_fet,
                                 use_fail=use_fail or use_faults)
    if ir_drop:
        att_p = column_ir_drop(jnp.sum(tp, axis=0), bl)
        att_n = column_ir_drop(jnp.sum(tn, axis=0), bl)
        if col_ok is None:
            att_mean = 0.5 * (jnp.mean(att_p) + jnp.mean(att_n))
        else:
            # dead bit lines read zero; the decode gain calibrates over
            # live columns only (same association as the device path so an
            # all-live plane stays bit-identical to the no-fault trace)
            att_p = att_p * col_ok
            att_n = att_n * col_ok
            live = jnp.maximum(jnp.sum(col_ok), 1.0)
            att_mean = 0.5 * (jnp.sum(att_p) / live + jnp.sum(att_n) / live)
    else:
        ok = jnp.float32(1.0) if col_ok is None else col_ok
        att_p = jnp.ones((n_cols,), jnp.float32) * ok
        att_n = jnp.ones((n_cols,), jnp.float32) * ok
        att_mean = jnp.float32(1.0)

    g_rms = None
    if not has_imax:
        g_diff = att_p[None, :] * tp - att_n[None, :] * tn
        g_rms = jnp.sqrt(jnp.mean(g_diff * g_diff))
    return wn, fail, att_p, att_n, att_mean, w_scale, g_rms


def _fake_aux(att_p, att_n, i_max, dec, scal: Dict[str, jnp.ndarray]):
    """The (8, N) aux plane of one crossbar (``ROW_*`` layout)."""
    full = functools.partial(jnp.full, att_p.shape, dtype=jnp.float32)
    rows = [None] * AUX_ROWS
    rows[ROW_ATT_POS], rows[ROW_ATT_NEG] = att_p, att_n
    rows[ROW_I_MAX], rows[ROW_DECODE] = full(i_max), full(dec)
    rows[ROW_G_AP], rows[ROW_G_FS] = full(scal["g_ap"]), full(scal["g_fs"])
    rows[ROW_G_SCALE], rows[ROW_R_ACCESS] = (full(scal["g_scale"]),
                                             full(scal["r_access"]))
    return jnp.stack(rows)


def _fake_operands(x, w, bl: BitlineParams, scal: Dict[str, jnp.ndarray], *,
                   apply_fet: bool, use_fail: bool, ir_drop: bool,
                   has_imax: bool, decode: bool, use_faults: bool = False,
                   repair: Optional[RepairPolicy] = None):
    """Traced operand preamble of the fake-analog ``x @ w``: the
    ``(v, wn, fail, aux)`` the fused kernel consumes.

    Everything numeric mirrors ``program_weights`` / ``kernel_operands`` /
    ``analog_matmul`` step for step, with host floats replaced by traced
    scalars (``scal``) so the whole chain jits."""
    wn, fail, att_p, att_n, att_mean, w_scale, g_rms = _fake_plane(
        w, bl, scal, apply_fet=apply_fet, use_fail=use_fail, ir_drop=ir_drop,
        has_imax=has_imax, use_faults=use_faults, repair=repair)
    x = jnp.asarray(x, jnp.float32)
    x_scale = jnp.max(jnp.abs(x))
    x_scale = jnp.where(x_scale == 0.0, 1.0, x_scale)
    v = scal["v_read"] * x / x_scale

    if has_imax:
        i_max = scal["i_max"]
    else:
        v_rms = jnp.sqrt(jnp.mean(v * v))
        i_sigma = v_rms * g_rms * math.sqrt(wn.shape[0])
        i_max = _round_2sig(jnp.maximum(scal["fs_sigmas"] * i_sigma, 1e-30))
    dec = ((x_scale * w_scale) / (scal["v_read"] * scal["g_fs"] * att_mean)
           if decode else jnp.float32(1.0))
    return v, wn, fail, _fake_aux(att_p, att_n, i_max, dec, scal)


def _fake_grouped_operands(x, w, group_sizes, bl: BitlineParams,
                           scal: Dict[str, jnp.ndarray], *, apply_fet: bool,
                           use_fail: bool, ir_drop: bool, has_imax: bool,
                           decode: bool, use_faults: bool = False,
                           repair: Optional[RepairPolicy] = None):
    """``_fake_operands`` of a stack of crossbars (one per expert) over rows
    sorted by expert: ``(v, wn, fail, aux)`` with ``wn``/``fail`` (G, K, N)
    and ``aux`` (G, 8, N).  The weight side is ``_fake_plane`` mapped over
    the stack; each expert's input scale max|x| (and the rms behind its ADC
    full scale) is taken over that expert's own rows."""
    n_groups, k_rows, _ = w.shape
    wn, fail, att_p, att_n, att_mean, w_scale, g_rms = jax.vmap(
        functools.partial(_fake_plane, bl=bl, scal=scal, apply_fet=apply_fet,
                          use_fail=use_fail, ir_drop=ir_drop,
                          has_imax=has_imax, use_faults=use_faults,
                          repair=repair))(w)
    x = jnp.asarray(x, jnp.float32)
    gid = group_ids(group_sizes, x.shape[0])        # n_groups past the total
    seg = functools.partial(jax.ops.segment_max, segment_ids=gid,
                            num_segments=n_groups + 1)
    x_scale = seg(jnp.max(jnp.abs(x), axis=1))[:n_groups]
    x_scale = jnp.where(x_scale > 0.0, x_scale, 1.0)   # 0 or no rows
    row_scale = jnp.append(x_scale, 1.0)[gid]
    v = scal["v_read"] * x / row_scale[:, None]
    v = jnp.where((gid < n_groups)[:, None], v, 0.0)

    if has_imax:
        i_max = jnp.full((n_groups,), scal["i_max"])
    else:
        ssq = jax.ops.segment_sum(jnp.sum(v * v, axis=1), gid,
                                  num_segments=n_groups + 1)[:n_groups]
        v_rms = jnp.sqrt(ssq / jnp.maximum(group_sizes * k_rows, 1))
        i_sigma = v_rms * g_rms * math.sqrt(k_rows)
        i_max = _round_2sig(jnp.maximum(scal["fs_sigmas"] * i_sigma, 1e-30))
    dec = ((x_scale * w_scale) / (scal["v_read"] * scal["g_fs"] * att_mean)
           if decode else jnp.ones((n_groups,), jnp.float32))
    aux = jax.vmap(_fake_aux, in_axes=(0, 0, 0, 0, None))(
        att_p, att_n, i_max, dec, scal)
    return v, wn, fail, aux


def _fake_mvm_body(x, w, bl: BitlineParams, scal: Dict[str, jnp.ndarray], *,
                   adc_bits: int, apply_fet: bool, use_fail: bool,
                   ir_drop: bool, has_imax: bool, decode: bool,
                   interpret: bool, use_faults: bool = False,
                   repair: Optional[RepairPolicy] = None,
                   name: str = "fake_analog"):
    """Traced fake-analog ``x @ w``: operand preamble + fused kernel, the
    kernel named ``name`` in the program and its traces."""
    v, wn, fail, aux = _fake_operands(
        x, w, bl, scal, apply_fet=apply_fet, use_fail=use_fail,
        ir_drop=ir_drop, has_imax=has_imax, decode=decode,
        use_faults=use_faults, repair=repair)
    return fake_analog_mac_pallas(v, wn, fail, aux, adc_bits=adc_bits,
                                  apply_fet=apply_fet,
                                  use_fail=use_fail or use_faults,
                                  interpret=interpret, name=name)


def _fake_grouped_body(x, w, group_sizes, scal: Dict[str, jnp.ndarray], *,
                       adc_bits: int, apply_fet: bool, use_fail: bool,
                       ir_drop: bool, has_imax: bool, decode: bool,
                       interpret: bool, use_faults: bool = False,
                       repair: Optional[RepairPolicy] = None,
                       name: str = "fake_analog_grouped"):
    """Traced fake-analog grouped product (``models.common.grouped_linear``):
    one crossbar per expert, rows sorted by expert, through the grouped
    fused kernel named ``name``."""
    bl = BitlineParams(rows=w.shape[1])
    v, wn, fail, aux = _fake_grouped_operands(
        x, w, group_sizes, bl, scal, apply_fet=apply_fet, use_fail=use_fail,
        ir_drop=ir_drop, has_imax=has_imax, decode=decode,
        use_faults=use_faults, repair=repair)
    return fake_analog_grouped_pallas(v, wn, fail, aux, group_sizes,
                                      adc_bits=adc_bits, apply_fet=apply_fet,
                                      use_fail=use_fail or use_faults,
                                      interpret=interpret, name=name)


@functools.lru_cache(maxsize=None)
def _jitted_fake_mvm(adc_bits: int, apply_fet: bool, use_fail: bool,
                     ir_drop: bool, has_imax: bool, decode: bool,
                     interpret: bool, use_faults: bool = False,
                     repair: Optional[RepairPolicy] = None):
    body = functools.partial(_fake_mvm_body, adc_bits=adc_bits,
                             apply_fet=apply_fet, use_fail=use_fail,
                             ir_drop=ir_drop, has_imax=has_imax,
                             decode=decode, interpret=interpret,
                             use_faults=use_faults, repair=repair)
    return jax.jit(body)


def _fake_faults_mode(cfg: AnalogConfig) -> bool:
    """Whether the fused path should trace the fault machinery in.  Presence
    of a spec switches it on (an all-zero-rate spec is the empty defect map,
    pinned bit-identical to ``faults=None``); drift is device-path only —
    same contract as D2D sigma in ``_systematic_g_scale``."""
    if cfg.faults is None:
        return False
    if cfg.faults.drift_sigma > 0.0:
        raise NotImplementedError(
            "fake-analog path models hard fault codes only; conductance "
            "drift draws per-cell host-side factors — use mode='device'")
    return True


def _systematic_g_scale(cfg: AnalogConfig) -> Tuple[bool, float]:
    """(apply_fet, 1/r_factor) for the fake path — systematic corners only.
    D2D spreads draw per-cell host-side factors (``spec.lane_factors``) the
    fused kernel deliberately does not model; use mode="device" for those."""
    spec = _resolved_variation(cfg)
    if spec is None:
        return False, 1.0
    c = spec.corners[0]
    if c.sigma_alpha or c.sigma_b_aniso or c.sigma_volume or c.sigma_r:
        raise NotImplementedError(
            "fake-analog path models systematic process corners only; "
            "per-cell D2D spreads need the device path (mode='device')")
    return True, 1.0 / c.r_factor


def _fake_scalars(kind: str, cfg: AnalogConfig, bl: BitlineParams,
                  g_scale: float, i_max: Optional[float]
                  ) -> Dict[str, jnp.ndarray]:
    """The traced-scalar pack: same f32 roundings as ``program_weights``."""
    dev = _device_for(kind, cfg)
    fs = cfg.faults
    g_p_eff = float(cell_conductance(jnp.asarray(1.0 / dev.r_parallel), bl))
    g_ap_eff = float(cell_conductance(jnp.asarray(1.0 / dev.r_antiparallel), bl))
    return {
        "g_ap": jnp.float32(g_ap_eff),
        "g_fs": jnp.float32(g_p_eff - g_ap_eff),
        "g_scale": jnp.float32(g_scale),
        "r_access": jnp.float32(bl.r_access),
        "v_read": jnp.float32(cfg.v_read),
        "fs_sigmas": jnp.float32(cfg.full_scale_sigmas),
        "ber": jnp.float32(cfg.write_ber),
        "seed": jnp.int32(cfg.seed),
        "i_max": jnp.float32(0.0 if i_max is None else i_max),
        # hard-fault plane knobs (DESIGN.md §13) — data, not compile keys,
        # so a fault-rate sweep reuses one executable; zeros when no spec
        "f_seed": jnp.uint32(0 if fs is None else fs.seed & 0xFFFFFFFF),
        "f_on": jnp.float32(0.0 if fs is None else fs.stuck_on_rate),
        "f_off": jnp.float32(0.0 if fs is None else fs.stuck_off_effective),
        "f_drow": jnp.float32(0.0 if fs is None else fs.dead_row_rate),
        "f_dcol": jnp.float32(0.0 if fs is None else fs.dead_col_rate),
    }


def fake_analog_matmul(
    w: jnp.ndarray,                  # (K, N) float weights
    x: jnp.ndarray,                  # (M, K) activations (signed)
    kind: str = "afmtj",
    cfg: AnalogConfig = AnalogConfig(),
    bl: Optional[BitlineParams] = None,
    i_max: Optional[float] = None,   # explicit ADC full scale (parity pins)
    decode: bool = True,             # False: raw quantized currents
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """``x @ w`` through the fused fake-analog kernel — the fast,
    fully-traced equivalent of ``program_weights`` + ``analog_matmul``,
    parity-pinned in ``tests/test_analog_pipeline.py``."""
    assert w.ndim == 2 and x.ndim == 2 and x.shape[1] == w.shape[0], (
        x.shape, w.shape)
    bl = bl or BitlineParams(rows=w.shape[0])
    apply_fet, g_scale = _systematic_g_scale(cfg)
    scal = _fake_scalars(kind, cfg, bl, g_scale, i_max)
    interp = _default_interpret() if interpret is None else interpret
    fn = _jitted_fake_mvm(cfg.adc_bits, apply_fet, cfg.write_ber > 0.0,
                          cfg.ir_drop, i_max is not None, decode, interp,
                          _fake_faults_mode(cfg), cfg.repair)
    return fn(x, w, bl, scal)


def fake_kernel_operands(
    w: jnp.ndarray,
    x: jnp.ndarray,
    kind: str = "afmtj",
    cfg: AnalogConfig = AnalogConfig(),
) -> Tuple[Tuple[jnp.ndarray, ...], Dict[str, Any]]:
    """The ``(v, wn, fail, aux)`` operands and the static kernel flags that
    ``fake_analog_matmul(w, x, kind, cfg)`` feeds the fused kernel — exposed
    so parity checks run ``ref.ref_fake_analog(*operands, **flags)`` on
    exactly the kernel's inputs instead of copying the preamble."""
    bl = BitlineParams(rows=w.shape[0])
    apply_fet, g_scale = _systematic_g_scale(cfg)
    use_faults = _fake_faults_mode(cfg)
    use_fail = cfg.write_ber > 0.0
    scal = _fake_scalars(kind, cfg, bl, g_scale, None)
    pre = jax.jit(functools.partial(
        _fake_operands, bl=bl, apply_fet=apply_fet, use_fail=use_fail,
        ir_drop=cfg.ir_drop, has_imax=False, decode=True,
        use_faults=use_faults, repair=cfg.repair))
    flags = dict(adc_bits=cfg.adc_bits, apply_fet=apply_fet,
                 use_fail=use_fail or use_faults)
    return pre(x, w, scal=scal), flags


# ---------------------------------------------------------------------------
# weight-programming cache (device path)
# ---------------------------------------------------------------------------
def _array_digest(a) -> str:
    a = np.ascontiguousarray(np.asarray(a, np.float32))
    h = hashlib.sha256(a.tobytes())
    h.update(str(a.shape).encode())
    return h.hexdigest()


def param_tree_hash(tree: Any) -> str:
    """Content hash of a parameter pytree, stable under dict-key insertion
    order (leaves are keyed by their canonical tree path)."""
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    payload = sorted((jax.tree_util.keystr(path), _array_digest(leaf))
                     for path, leaf in leaves)
    return _cache.content_key({"params": payload})


def programming_key(w, kind: str, cfg: AnalogConfig,
                    bl: BitlineParams) -> str:
    """Content key over the *programming-relevant* axes only: sweeping
    ``adc_bits`` / ``full_scale_sigmas`` / ``v_read`` (pure read-out knobs)
    hits the cache; TMR / corner / BER / seed / IR-drop re-program.  The
    platform is keyed too, so a plane programmed on one is never served on
    another."""
    spec = _resolved_variation(cfg)
    return _cache.content_key({
        "v": PROGRAMMING_VERSION,
        "platform": jax.devices()[0].platform,
        "kind": kind,
        "w": _array_digest(w),
        "tmr": cfg.tmr,
        "ir_drop": cfg.ir_drop,
        "seed": cfg.seed,
        "write_ber": cfg.write_ber,
        "variation": None if spec is None else {
            "corners": [dataclasses.asdict(c) for c in spec.corners],
            "seed": spec.seed,
            "distribution": spec.distribution,
        },
        "faults": (None if cfg.faults is None
                   else dataclasses.asdict(cfg.faults)),
        "repair": (None if cfg.repair is None
                   else dataclasses.asdict(cfg.repair)),
        "bitline": dataclasses.asdict(bl),
    })


def program_weights_cached(
    w: jnp.ndarray,
    kind: str = "afmtj",
    cfg: AnalogConfig = AnalogConfig(),
    bl: Optional[BitlineParams] = None,
    cache_dir: Optional[str] = None,
) -> ProgrammedArray:
    """``program_weights`` behind the content-keyed store: a cache hit
    returns the identical conductance plane + calibration scalars without
    touching the programming chain."""
    bl = bl or BitlineParams(rows=w.shape[0])
    key = programming_key(w, kind, cfg, bl)
    hit = _cache.load_arrays(key, cache_dir)
    if hit is not None and "g_diff" in hit:
        s = hit["scalars"]
        return ProgrammedArray(
            g_diff=jnp.asarray(hit["g_diff"], jnp.float32),
            w_scale=float(s[0]), g_fs=float(s[1]), att_mean=float(s[2]),
            g_rms=float(s[3]), dev=_device_for(kind, cfg), bl=bl, cfg=cfg)
    arr = program_weights(w, kind, cfg, bl)
    _cache.store_arrays(
        key,
        {"g_diff": np.asarray(arr.g_diff, np.float32),
         "scalars": np.asarray([arr.w_scale, arr.g_fs, arr.att_mean,
                                arr.g_rms], np.float64)},
        {"kind": kind, "shape": list(arr.g_diff.shape), "tmr": cfg.tmr,
         "seed": cfg.seed, "write_ber": cfg.write_ber, "key": key},
        cache_dir)
    return arr


# ---------------------------------------------------------------------------
# unrolled model forward + interception hooks
# ---------------------------------------------------------------------------
def _forward_unrolled(params, cfg: ArchConfig, tokens: jnp.ndarray,
                      routes=None):
    """Full-sequence logits via an eager layer unroll (no lax.scan — the
    device-path hook reduces to host floats, which cannot cross a scan).
    Decoder-only: same blocks as ``forward_train``, leading dense layers
    first, MoE layers dropless; full logits returned.  ``routes``, if a
    list, gets each MoE layer's expert ids and rows per held expert
    (``ffn.moe_ffn_dropless``).  Device ops carry their layer in
    ``op_name``: ``block{i}/attn`` (``block{i}/mla``), ``block{i}/ffn``
    (``block{i}/moe``) and ``unembed`` (the output head)."""
    assert cfg.n_encoder_layers == 0, "analog routing covers decoder-only"
    x = model_mod._embed(params, cfg, tokens)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    stacks = [(params["blocks"], cfg.pattern, cfg.n_pattern_repeats)]
    if cfg.first_k_dense:
        stacks.insert(0, (params["lead"], cfg.lead_pattern,
                          cfg.first_k_dense))
    layer = 0
    for stack, pattern, reps in stacks:
        for rep in range(reps):
            lp = jax.tree_util.tree_map(lambda a: a[rep], stack)
            for i, (mixer, f) in enumerate(pattern):
                with jax.named_scope(f"block{layer}"):
                    x, _ = model_mod._run_block(lp[f"pos{i}"], x, cfg, mixer,
                                                f, positions, routes=routes,
                                                dropless=True)
                layer += 1
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    with jax.named_scope("unembed"):
        return model_mod._logits(params, cfg, x)


def _stacked_routes(routes):
    """The MoE layers' routes as arrays: ids (layers, tokens, top_k) and
    sizes (layers, held experts)."""
    return {k: jnp.stack([r[k] for r in routes]) for k in ("ids", "sizes")}


def grouped_plan(cfg: ArchConfig, tokens: int) -> Tuple[int, int]:
    """(grouped kernel launches, rows handed to them) of one analog forward
    over ``tokens`` positions: three expert products per MoE layer, each
    over the layer's (token, choice) pair buffer.  Static: the host knows
    it without reading the device."""
    if cfg.moe is None:
        return 0, 0
    n_moe = cfg.n_pattern_repeats * sum(f == "moe" for _, f in cfg.pattern)
    return 3 * n_moe, 3 * n_moe * tokens * cfg.moe.top_k


def model_forward_logits(params, cfg: ArchConfig, tokens, hook=None):
    """Eager unrolled forward; ``hook(x2d, w, tag)`` intercepts every
    linear (None = exact f32 reference)."""
    if hook is None:
        return _forward_unrolled(params, cfg, tokens)
    with intercept_linears(hook):
        return _forward_unrolled(params, cfg, tokens)


@functools.lru_cache(maxsize=None)
def _jitted_ref_forward(cfg: ArchConfig):
    return jax.jit(lambda params, tokens: _forward_unrolled(params, cfg,
                                                            tokens))


@functools.lru_cache(maxsize=None)
def _jitted_fake_forward(cfg: ArchConfig, adc_bits: int, apply_fet: bool,
                         use_fail: bool, ir_drop: bool, interpret: bool,
                         use_faults: bool = False,
                         repair: Optional[RepairPolicy] = None,
                         with_routes: bool = False):
    """Whole forward jitted with the fake-analog hooks traced in: one XLA
    executable per (arch, adc_bits[, repair policy]) — TMR/corner/BER/seed
    and the fault rates arrive as data.  ``with_routes``: it returns
    ``(logits, routes)`` (``_stacked_routes``) for a model with MoE
    layers."""
    flags = dict(adc_bits=adc_bits, apply_fet=apply_fet, use_fail=use_fail,
                 ir_drop=ir_drop, has_imax=False, decode=True,
                 interpret=interpret, use_faults=use_faults, repair=repair)
    body = functools.partial(_fake_mvm_body, **flags)
    grouped_body = functools.partial(_fake_grouped_body, **flags)

    @jax.jit
    def run(params, tokens, scal):
        # rows = K of each site, like the device path's per-layer
        # BitlineParams — shapes are static at trace time, so every site
        # bakes its own IR line length into the one executable; each
        # kernel is named after its site (``fake_analog_unembed``,
        # ``fake_analog_moe_w_up``), which a device trace shows
        def hook(x2, w, tag):
            return body(x2, w, BitlineParams(rows=w.shape[0]), scal,
                        name=f"fake_analog_{tag}" if tag else "fake_analog")

        def grouped(x2, w, sizes, tag):
            return grouped_body(x2, w, sizes, scal, name=f"fake_analog_{tag}")

        routes = [] if with_routes else None
        with intercept_linears(hook, grouped):
            logits = _forward_unrolled(params, cfg, tokens, routes)
        return (logits, _stacked_routes(routes)) if with_routes else logits

    return run


@functools.lru_cache(maxsize=None)
def _jitted_bnn_forward(cfg: ArchConfig, tie: int, interpret: bool):
    @jax.jit
    def run(params, tokens):
        with intercept_linears(
                lambda x2, w, tag: binary_matmul(x2, w, tie=tie,
                                                 interpret=interpret)):
            return _forward_unrolled(params, cfg, tokens)

    return run


def analog_model_logits(
    params, cfg: ArchConfig, tokens,
    acfg: AnalogConfig = AnalogConfig(),
    kind: str = "afmtj",
    mode: str = "fake",              # fake | device | bnn
    tie: int = 1,
    cache_dir: Optional[str] = None,
    interpret: Optional[bool] = None,
    with_routes: bool = False,
):
    """Full-sequence logits with every linear (and every expert product)
    routed through the analog MVM.  The fake path runs inside the host
    spans ``repro.analog.prepare`` and ``repro.analog.dispatch`` (DESIGN.md
    §15) and counts its grouped launches; with ``with_routes`` it returns
    ``(logits, routes)``: each MoE layer's expert ids (layers, tokens,
    top_k) and rows per held expert (layers, held), as device arrays."""
    interp = _default_interpret() if interpret is None else interpret
    if mode == "fake":
        with telemetry.span("analog.prepare"):
            apply_fet, g_scale = _systematic_g_scale(acfg)
            fn = _jitted_fake_forward(cfg, acfg.adc_bits, apply_fet,
                                      acfg.write_ber > 0.0, acfg.ir_drop,
                                      interp, _fake_faults_mode(acfg),
                                      acfg.repair,
                                      with_routes and cfg.moe is not None)
            # device constants are rows-independent (the FET series
            # combination has no wire term), so one scalar pack serves
            # every layer
            scal = _fake_scalars(kind, acfg, BitlineParams(), g_scale, None)
        batch, seq = jnp.shape(tokens)
        launches, rows = grouped_plan(cfg, batch * seq)
        if launches:
            telemetry.count("analog.grouped_launches", launches)
            telemetry.count("analog.grouped_rows", rows)
        with telemetry.span("analog.dispatch", batch=batch, seq=seq,
                            adc_bits=acfg.adc_bits):
            return fn(params, tokens, scal)
    assert not with_routes, "routes come with mode='fake'"
    if mode == "bnn":
        return _jitted_bnn_forward(cfg, tie, interp)(params, tokens)
    if mode == "device":
        def hook(x2, w, tag):
            arr = program_weights_cached(w, kind, acfg,
                                         BitlineParams(rows=w.shape[0]),
                                         cache_dir)
            return analog_matmul(arr, x2, interpret=interp)

        return model_forward_logits(params, cfg, tokens, hook)
    raise ValueError(f"unknown mode {mode!r}")


# ---------------------------------------------------------------------------
# accuracy metrics + surfaces
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ModelAccuracyReport:
    """Model-level accuracy of one analog configuration point."""

    arch: str
    kind: str
    mode: str                      # fake | device | bnn
    adc_bits: int
    tmr: float
    corner: str                    # systematic process corner name
    write_ber: float
    kl: float                      # mean KL(ref || analog) over positions
    token_match: float             # greedy-argmax agreement rate
    ppl_analog: float              # next-token perplexity, analog logits
    ppl_ref: float                 # next-token perplexity, exact logits
    batch: int
    seq_len: int
    fault_rate: float = 0.0        # headline hard-fault rate (FaultSpec.rate)
    repair: str = "none"           # repair policy name


def logit_metrics(ref_logits, ana_logits, tokens
                  ) -> Tuple[float, float, float, float]:
    """(kl, token_match, ppl_analog, ppl_ref) from two (B, S, V) logit sets."""
    lr = jax.nn.log_softmax(jnp.asarray(ref_logits, jnp.float32), axis=-1)
    la = jax.nn.log_softmax(jnp.asarray(ana_logits, jnp.float32), axis=-1)
    p = jnp.exp(lr)
    kl = float(jnp.mean(jnp.sum(p * (lr - la), axis=-1)))
    match = float(jnp.mean(
        (jnp.argmax(la, axis=-1) == jnp.argmax(lr, axis=-1))
        .astype(jnp.float32)))

    def ppl(lp):
        gold = jnp.take_along_axis(lp[:, :-1],
                                   tokens[:, 1:][..., None], axis=-1)
        return float(jnp.exp(-jnp.mean(gold)))

    return kl, match, ppl(la), ppl(lr)


def _arch_config(arch: str, smoke: bool) -> ArchConfig:
    return smoke_config(arch) if smoke else get_arch(arch)


def _setup(arch: str, smoke: bool, batch: int, seq_len: int, seed: int):
    """(cfg, params, tokens, ref_logits) shared across surface points."""
    cfg = _arch_config(arch, smoke)
    params = model_mod.init_params(cfg, jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (batch, seq_len)),
                         jnp.int32)
    ref_logits = _jitted_ref_forward(cfg)(params, tokens)
    return cfg, params, tokens, ref_logits


def _corner_spec(corner: str, seed: int) -> Optional[VariationSpec]:
    if corner in ("", "tt"):
        # tt is the all-1.0 nominal corner: identical conductances with or
        # without the FET round trip, so skip the spec (and the recompile)
        return None
    return VariationSpec(corners=(PROCESS_CORNERS[corner],), seed=seed)


def model_accuracy(
    arch: str = "qwen2-0.5b",
    acfg: AnalogConfig = AnalogConfig(),
    kind: str = "afmtj",
    mode: str = "fake",
    corner: str = "tt",
    batch: int = 2,
    seq_len: int = 64,
    seed: int = 0,
    smoke: bool = True,
    tie: int = 1,
    cache_dir: Optional[str] = None,
    _setup_state=None,
) -> ModelAccuracyReport:
    """One surface point: route the forward through the analog path, score
    against the exact f32 logits on synthetic token sequences."""
    if _setup_state is None:
        _setup_state = _setup(arch, smoke, batch, seq_len, seed)
    cfg, params, tokens, ref_logits = _setup_state
    spec = _corner_spec(corner, acfg.seed)
    if spec is not None:
        acfg = dataclasses.replace(acfg, variation=spec)
    ana = analog_model_logits(params, cfg, tokens, acfg, kind=kind,
                              mode=mode, tie=tie, cache_dir=cache_dir)
    kl, match, ppl_a, ppl_r = logit_metrics(ref_logits, ana, tokens)
    tmr = acfg.tmr if acfg.tmr is not None else _device_for(kind, acfg).tmr
    fspec = acfg.faults
    frate = 0.0 if fspec is None else (fspec.rate or fspec.cell_fault_rate)
    return ModelAccuracyReport(
        arch=arch, kind=kind, mode=mode, adc_bits=acfg.adc_bits,
        tmr=float(tmr), corner=corner, write_ber=acfg.write_ber, kl=kl,
        token_match=match, ppl_analog=ppl_a, ppl_ref=ppl_r, batch=batch,
        seq_len=seq_len, fault_rate=float(frate),
        repair="none" if acfg.repair is None else acfg.repair.name)


def model_accuracy_surface(
    arch: str = "qwen2-0.5b",
    kind: str = "afmtj",
    mode: str = "fake",
    adc_bits: Sequence[int] = (4, 6, 8),
    tmrs: Sequence[Optional[float]] = (None,),
    corners: Sequence[str] = ("tt",),
    write_bers: Sequence[float] = (0.0,),
    fault_rates: Sequence[float] = (0.0,),
    repair: Optional[RepairPolicy] = None,
    batch: int = 2,
    seq_len: int = 64,
    seed: int = 0,
    smoke: bool = True,
    cache_dir: Optional[str] = None,
) -> Tuple[ModelAccuracyReport, ...]:
    """The model-level accuracy surface: full outer product of the
    non-ideality axes, model/params/reference set up once.  The default
    ``fault_rates=(0.0,)`` keeps the fault machinery out of the trace
    entirely (bit-identical to pre-fault surfaces)."""
    state = _setup(arch, smoke, batch, seq_len, seed)
    out = []
    for fr in fault_rates:
        fspec = None if fr == 0.0 else FaultSpec.at_rate(float(fr), seed=seed)
        for ber in write_bers:
            for corner in corners:
                for tmr in tmrs:
                    for bits in adc_bits:
                        acfg = AnalogConfig(
                            adc_bits=bits, tmr=tmr, write_ber=ber, seed=seed,
                            faults=fspec,
                            repair=repair if fspec is not None else None)
                        out.append(model_accuracy(
                            arch, acfg, kind=kind, mode=mode, corner=corner,
                            batch=batch, seq_len=seq_len, seed=seed,
                            smoke=smoke, cache_dir=cache_dir,
                            _setup_state=state))
    return tuple(out)


def model_degradation_curves(
    arch: str = "qwen2-0.5b",
    kind: str = "afmtj",
    rates: Sequence[float] = (0.0, 1e-3, 3e-3, 1e-2, 3e-2),
    policies: Sequence[Optional[RepairPolicy]] = (None,
                                                 hard_faults.REPAIR_SPARE),
    adc_bits: int = 6,
    mode: str = "fake",
    batch: int = 2,
    seq_len: int = 64,
    seed: int = 0,
    smoke: bool = True,
    cache_dir: Optional[str] = None,
) -> Tuple[ModelAccuracyReport, ...]:
    """Graceful-degradation curves: model accuracy vs fault rate x repair
    policy (DESIGN.md §13).  A ``FaultSpec`` is present at every point —
    including rate 0 — so each policy's whole rate sweep shares ONE XLA
    executable (rates are data; pinned in ``tests/test_faults.py``), and the
    counter-RNG keeps the defect maps CRN-paired across policies."""
    state = _setup(arch, smoke, batch, seq_len, seed)
    out = []
    for pol in policies:
        for r in rates:
            acfg = AnalogConfig(
                adc_bits=adc_bits, seed=seed,
                faults=FaultSpec.at_rate(float(r), seed=seed), repair=pol)
            out.append(model_accuracy(
                arch, acfg, kind=kind, mode=mode, batch=batch,
                seq_len=seq_len, seed=seed, smoke=smoke, cache_dir=cache_dir,
                _setup_state=state))
    return tuple(out)


def degradation_knee(reports: Sequence[ModelAccuracyReport],
                     min_token_match: float = 0.8) -> Dict[str, float]:
    """Per repair policy, the largest swept fault rate still meeting the
    accuracy bar — the knee where remapping stops saving accuracy.  (The
    CRN monotone coupling makes accuracy-vs-rate monotone per policy, so
    max-passing-rate is the knee.)"""
    knees: Dict[str, float] = {}
    for r in reports:
        knees.setdefault(r.repair, 0.0)
        if r.token_match >= min_token_match:
            knees[r.repair] = max(knees[r.repair], r.fault_rate)
    return knees

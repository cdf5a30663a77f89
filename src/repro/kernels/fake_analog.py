"""Pallas TPU kernel: fused fake-analog MVM (program->IR-drop->ADC in one pass).

The full device path (``imc.analog_pipeline``) materializes a programmed
conductance pair per weight matrix on the host — ``program_weights`` reduces
to Python floats (w_scale, att_mean, g_rms) and ``kernel_operands`` rounds
the ADC full scale through a *string*, so every surface point pays host
syncs plus a fresh ``_mvm_sharded`` compile (``i_max`` is a jit static).
That is fine for one projection; it is intractable for (layers x batch x
surface-points) model sweeps.

This kernel is the batched fast path: the differential-conductance
construction is replayed *inside* the matmul tile loop from the normalized
weights, so programming never materializes and the whole chain is traced —
one compile per (shape, adc_bits), sweep points are data.  Per (BK, BN)
tile, in order (bit-matching ``program_weights``):

  1. targets      — tp/tn = G_AP + max(+-wn, 0) * G_FS
  2. corner FET   — push through the access FET, scale the junction by the
                    systematic corner factor, come forward again (skipped
                    when no variation spec, exactly like the device path)
  3. write errors — failed cells drop to the G_AP floor (mask operand)
  4. IR drop      — per-column attenuation planes (precomputed column sums;
                    an (N,) reduction cannot live inside the K grid loop)
  5. MAC + ADC    — att_p*tp - att_n*tn, one MXU dot per tile, f32
                    accumulator scratch; epilogue quantizes through the
                    *shared* ``adc_quantize`` and applies the decode scale.

Scalars (ADC full scale, decode gain, device constants) ride in an (8, N)
aux plane so they stay traced data, not compile keys.  Zero-padding is
exact: padded K rows see v = 0 (no current), padded N columns carry att = 0
(g_diff = 0).  Numerical parity vs the device path is pinned in
``tests/test_analog_pipeline.py``; the jnp oracle is ``ref.ref_fake_analog``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bitline_mac import (BM, BN, BK, F32_DOT, _pad2,
                                       adc_quantize)

# aux plane row layout (8, N) — per-column planes first, broadcast scalars
# (stored across the full row) after
ROW_ATT_POS = 0     # per-column IR attenuation, positive array
ROW_ATT_NEG = 1     # per-column IR attenuation, negative array
ROW_I_MAX = 2       # ADC full-scale current [A]
ROW_DECODE = 3      # decode gain back to weight/activation units
ROW_G_AP = 4        # effective AP-state conductance (G_AP floor) [S]
ROW_G_FS = 5        # unit-weight differential conductance G_P - G_AP [S]
ROW_G_SCALE = 6     # systematic corner junction conductance factor 1/r_f
ROW_R_ACCESS = 7    # access transistor on-resistance [Ohm]
AUX_ROWS = 8

# ``fail``-plane bit codes.  The plane is f32 (it rides the same operand
# layout as the weight tile) carrying a bit-OR of small powers of two —
# exact in f32 up to 127.  Bits 1/2 are the PR-3 write-verify fail masks;
# bits 4..64 are the hard-fault codes drawn by ``imc.faults`` (stuck-at and
# dead-line defects are *data*, not compile keys).
FAIL_POS = 1        # write-verify fail: positive cell at the G_AP floor
FAIL_NEG = 2        # write-verify fail: negative cell at the G_AP floor
FAULT_POS_OFF = 4   # hard stuck-at-G_off: positive cell pinned at G_AP
FAULT_NEG_OFF = 8   # hard stuck-at-G_off: negative cell pinned at G_AP
FAULT_POS_ON = 16   # hard stuck-at-G_on: positive cell pinned at G_AP+G_FS
FAULT_NEG_ON = 32   # hard stuck-at-G_on: negative cell pinned at G_AP+G_FS
FAULT_DEAD = 64     # dead differential pair (dead row driver / repair mask)
FAIL_CODE_MAX = 127


def fail_bit(code, bit):
    """True where integer bit ``bit`` is set in the f32 ``fail`` code plane.

    Pure f32 arithmetic (floor/mod) so it lowers identically inside the
    Pallas tile, the jnp oracle, and the traced preamble."""
    return jnp.floor(code * (1.0 / bit)) % 2.0 >= 1.0


def pos_neg_conductance(wn, fail, g_ap, g_fs, g_scale, r_access, *,
                        apply_fet: bool, use_fail: bool):
    """Per-cell (g_pos, g_neg) pre-IR-drop conductances — the fused replay of
    ``program_weights`` steps 1-3.  Shared by the kernel tile, the jnp
    oracle, and the traced preamble that reduces the column sums for the IR
    planes (``imc.model_analog``), so the cell math cannot drift."""
    tp = g_ap + jnp.maximum(wn, 0.0) * g_fs
    tn = g_ap + jnp.maximum(-wn, 0.0) * g_fs
    if apply_fet:
        def fet(t):
            g_j = (t / (1.0 - r_access * t)) * g_scale
            return g_j / (1.0 + r_access * g_j)

        tp, tn = fet(tp), fet(tn)
    if use_fail:
        # Decode order fixes the fault priority: G_AP floors (write-verify
        # fails + stuck-off), then stuck-on overrides, then dead pairs kill
        # the cell outright.  For legacy codes {0,1,2,3} this is bit-for-bit
        # the old two-way decode (bit 1 <-> fail in {1,3}; bit 2 <-> >= 2).
        g_ap_b = jnp.broadcast_to(g_ap, tp.shape)
        g_on_b = jnp.broadcast_to(g_ap + g_fs, tp.shape)
        tp = jnp.where(fail_bit(fail, FAIL_POS) | fail_bit(fail, FAULT_POS_OFF),
                       g_ap_b, tp)
        tn = jnp.where(fail_bit(fail, FAIL_NEG) | fail_bit(fail, FAULT_NEG_OFF),
                       g_ap_b, tn)
        tp = jnp.where(fail_bit(fail, FAULT_POS_ON), g_on_b, tp)
        tn = jnp.where(fail_bit(fail, FAULT_NEG_ON), g_on_b, tn)
        dead = fail_bit(fail, FAULT_DEAD)
        tp = jnp.where(dead, 0.0, tp)
        tn = jnp.where(dead, 0.0, tn)
    return tp, tn


def _tile_g_diff(wn, fail, aux, *, apply_fet: bool, use_fail: bool):
    """(BK, BN) differential conductance tile from the aux plane.

    The scalars are read as whole ``(1, BN)`` rows (they are stored across
    the row): Mosaic cannot broadcast a ``(1, 1)`` slice in both sublanes
    and lanes.  Padded columns read zeros and carry att = 0."""
    def row(r):
        return aux[r:r + 1, :]

    tp, tn = pos_neg_conductance(
        wn, fail, row(ROW_G_AP), row(ROW_G_FS), row(ROW_G_SCALE),
        row(ROW_R_ACCESS), apply_fet=apply_fet, use_fail=use_fail)
    return row(ROW_ATT_POS) * tp - row(ROW_ATT_NEG) * tn


def _fake_kernel(v_ref, w_ref, fail_ref, aux_ref, o_ref, acc_ref, *, nk: int,
                 adc_bits: int, apply_fet: bool, use_fail: bool):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g_diff = _tile_g_diff(w_ref[...], fail_ref[...], aux_ref[...],
                          apply_fet=apply_fet, use_fail=use_fail)
    acc_ref[...] += jnp.dot(
        v_ref[...], g_diff, precision=F32_DOT,
        preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == nk - 1)
    def _epilogue():
        aux = aux_ref[...]
        i_max = aux[ROW_I_MAX:ROW_I_MAX + 1, :]
        dec = aux[ROW_DECODE:ROW_DECODE + 1, :]
        i_bl = adc_quantize(acc_ref[...], adc_bits, i_max)
        o_ref[...] = (i_bl * dec).astype(o_ref.dtype)


def fake_analog_mac_pallas(
    v: jnp.ndarray,               # (M, K) read voltages (batch x rows)
    wn: jnp.ndarray,              # (K, N) normalized weights in [-1, 1]
    fail: jnp.ndarray,            # (K, N) f32 fail/fault bit codes [0, 127]
    aux: jnp.ndarray,             # (8, N) f32 aux plane (ROW_* layout)
    adc_bits: int = 0,
    apply_fet: bool = False,
    use_fail: bool = False,
    interpret: bool = False,
    name: str = "fake_analog",    # the kernel's name in the program's HLO
) -> jnp.ndarray:
    M, K = v.shape
    K2, N = wn.shape
    assert K == K2, (v.shape, wn.shape)
    assert fail.shape == wn.shape, (fail.shape, wn.shape)
    assert aux.shape == (AUX_ROWS, N), (aux.shape, N)
    assert adc_bits == 0 or adc_bits >= 2, adc_bits
    from jax.experimental.pallas import tpu as pltpu

    v = _pad2(v, BM, BK)
    wn = _pad2(wn, BK, BN)
    fail = _pad2(fail, BK, BN)
    aux = _pad2(aux, AUX_ROWS, BN)
    mp, kp = v.shape
    _, np_ = wn.shape
    nk = kp // BK
    kern = functools.partial(_fake_kernel, nk=nk, adc_bits=adc_bits,
                             apply_fet=apply_fet, use_fail=use_fail)
    out = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        grid=(mp // BM, np_ // BN, nk),
        in_specs=[
            pl.BlockSpec((BM, BK), lambda i, j, k: (i, k)),
            pl.BlockSpec((BK, BN), lambda i, j, k: (k, j)),
            pl.BlockSpec((BK, BN), lambda i, j, k: (k, j)),
            pl.BlockSpec((AUX_ROWS, BN), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((BM, BN), lambda i, j, k: (i, j)),
        scratch_shapes=[pltpu.VMEM((BM, BN), jnp.float32)],
        interpret=interpret,
        name=name,
    )(v, wn, fail, aux)
    if (mp, np_) != (M, N):
        out = out[:M, :N]
    return out


def _grouped_kernel(tile_group_ref, v_ref, w_ref, fail_ref, aux_ref, o_ref,
                    acc_ref, **kw):
    """The fused kernel's tile step on one row tile of one group: the group's
    weight, fail and aux blocks were picked by the scalar-prefetched
    ``tile_group`` in the index maps, so the tile math is ``_fake_kernel``'s
    own."""
    del tile_group_ref
    _fake_kernel(v_ref, w_ref, fail_ref, aux_ref, o_ref, acc_ref, **kw)


def _pad_last2(x: jnp.ndarray, m: int, n: int) -> jnp.ndarray:
    pm, pn = -x.shape[-2] % m, -x.shape[-1] % n
    if pm or pn:
        x = jnp.pad(x, ((0, 0), (0, pm), (0, pn)))
    return x


GROUPED_TILE_MAX = 512


def grouped_row_tile(rows: int, groups: int) -> int:
    """Row tile of the grouped kernel over a ``rows``-row buffer shared by
    ``groups`` crossbars: the power of two at or above a group's mean
    share, from ``BM`` to ``GROUPED_TILE_MAX``."""
    mean = max(1, -(-rows // groups))
    return min(GROUPED_TILE_MAX, max(BM, 1 << (mean - 1).bit_length()))


def fake_analog_grouped_pallas(
    v: jnp.ndarray,               # (M, K) read voltages, rows sorted by group
    wn: jnp.ndarray,              # (G, K, N) normalized weights per group
    fail: jnp.ndarray,            # (G, K, N) f32 fail/fault bit codes
    aux: jnp.ndarray,             # (G, 8, N) aux plane per group
    group_sizes: jnp.ndarray,     # (G,) rows of each group, in order
    adc_bits: int = 0,
    apply_fet: bool = False,
    use_fail: bool = False,
    interpret: bool = False,
    name: str = "fake_analog_grouped",
) -> jnp.ndarray:
    """Grouped (ragged) fused fake-analog MVM: rows of group ``g`` read
    crossbar ``g`` (its own planes and scales).  Rows past the groups' total
    come back zero.

    Each group's rows are laid out from a row-tile boundary, so every row
    tile belongs to one group; the tile -> group map is scalar-prefetched
    and picks the group's weight, fail and aux blocks in the index maps.
    The grid's row axis is the number of tiles the groups fill (a traced
    bound), so tiles past the routed total are never run.  A row tile
    streams its group's whole weight and fail planes, so the tile is
    ``grouped_row_tile`` rows: about one tile per group at a few hundred
    rows a group."""
    M, K = v.shape
    G, K2, N = wn.shape
    assert K == K2, (v.shape, wn.shape)
    assert fail.shape == wn.shape, (fail.shape, wn.shape)
    assert aux.shape == (G, AUX_ROWS, N), (aux.shape, G, N)
    assert group_sizes.shape == (G,), (group_sizes.shape, G)
    assert adc_bits == 0 or adc_bits >= 2, adc_bits
    from jax.experimental.pallas import tpu as pltpu

    bm = grouped_row_tile(M, G)
    sizes = group_sizes.astype(jnp.int32)
    tiles = (sizes + bm - 1) // bm
    tile_end = jnp.cumsum(tiles)
    tile_start = tile_end - tiles
    row_end = jnp.cumsum(sizes)
    row_start = row_end - sizes
    max_tiles = -(-M // bm) + G
    tile_group = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(max_tiles), side="right"),
        G - 1).astype(jnp.int32)

    # gather the rows into the tile-aligned layout (empty slots read 0 V)
    slot = jnp.arange(max_tiles * bm)
    g = tile_group[slot // bm]
    j = slot - tile_start[g] * bm
    valid = (slot // bm < tile_end[-1]) & (j < sizes[g])
    v_al = jnp.where(valid[:, None], v[jnp.where(valid, row_start[g] + j, 0)],
                     0.0)
    v_al = _pad2(v_al, bm, BK)
    wn = _pad_last2(wn, BK, BN)
    fail = _pad_last2(fail, BK, BN)
    aux = _pad_last2(aux, AUX_ROWS, BN)
    _, kp, np_ = wn.shape
    nk = kp // BK
    kern = functools.partial(_grouped_kernel, nk=nk, adc_bits=adc_bits,
                             apply_fet=apply_fet, use_fail=use_fail)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(jnp.maximum(tile_end[-1], 1), np_ // BN, nk),
        in_specs=[
            pl.BlockSpec((bm, BK), lambda i, j, k, tg: (i, k)),
            pl.BlockSpec((None, BK, BN), lambda i, j, k, tg: (tg[i], k, j)),
            pl.BlockSpec((None, BK, BN), lambda i, j, k, tg: (tg[i], k, j)),
            pl.BlockSpec((None, AUX_ROWS, BN),
                         lambda i, j, k, tg: (tg[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, BN), lambda i, j, k, tg: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, BN), jnp.float32)],
    )
    out = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((v_al.shape[0], np_), jnp.float32),
        grid_spec=grid_spec,
        interpret=interpret,
        name=name,
    )(tile_group, v_al, wn, fail, aux)

    # back to the sorted row order; rows past the total read zero
    gid = jnp.searchsorted(row_end, jnp.arange(M), side="right")
    gc = jnp.minimum(gid, G - 1)
    back = tile_start[gc] * bm + jnp.arange(M) - row_start[gc]
    return jnp.where((gid < G)[:, None], out[back, :N], 0.0)

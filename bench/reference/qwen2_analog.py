"""Plain reference of a dense GQA decoder forward with every linear routed
through a differential-conductance crossbar read by an ADC.

Written from the published architecture (qwen2: RMSNorm, rotary
embeddings, grouped-query attention with QKV bias, SiLU-gated FFN, tied
embeddings; arXiv:2407.10671) and the analog read model's stated math, in
``jax.numpy``; it imports nothing of the system under test.

The analog linear ``y = x @ w`` of a (M, K) activation block:

  wn = w / max|w|;  g+ = G_ap + max(wn, 0) G_fs,  g- = G_ap + max(-wn, 0) G_fs
  att+- = 1 / (1 + r_wire K/2 * column sum of g+-)        (bit-line IR drop)
  v = v_read x / max|x|;  g = att+ g+ - att- g-
  i_max = (4 sigma of the column current, rounded to 2 significant digits)
  y = round(clip(v @ g / i_max, -1, 1) * (2^(b-1) - 1)) / (2^(b-1) - 1)
      * i_max * max|x| max|w| / (v_read G_fs mean(att))

(with an ideal converter, b = 0, the current v @ g is decoded unrounded)

with G_ap and G_fs the cells' conductances behind the access transistor.
The batch statistics (max|x|, the rms of v) span every row of the block,
so the output head is prepared once over all positions and then evaluated
in row blocks.

Dtypes follow the configuration: weights float32; the embedding lookup in
the compute dtype; each operation keeps the dtype of its input, and the
crossbar reads return float32 (so the residual stream is float32 from the
first attention block on).  ``mode`` is the precision of every product:
``"highest"`` (float32 products, as the configuration states) or ``"high"``
(three bfloat16 passes), the control that a sound check must reject: on
a TPU its own ``Precision.HIGH``; elsewhere, where no three-pass product
exists, the operands split into a bfloat16 head and tail and the
tail-by-tail product dropped.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def cell_constants(dev: dict, r_access: float):
    """(G_ap, G_fs) as float32: the junction's parallel and antiparallel
    conductances in series with the access transistor."""
    area = dev["lx"] * dev["ly"]
    r_p = dev["ra_product"] / area
    r_ap = r_p * (1.0 + dev["tmr"])

    def cell(g):
        g = np.float32(g)
        return np.float32(g / (np.float32(1.0) + np.float32(r_access) * g))

    g_p, g_ap = cell(1.0 / r_p), cell(1.0 / r_ap)
    return np.float32(g_ap), np.float32(float(g_p) - float(g_ap))


def _round_2sig(v):
    e = jnp.floor(jnp.log10(v))
    p = 10.0 ** (e - 1.0)
    return jnp.round(v / p) * p


def prepare(x, w, a: dict):
    """The crossbar's programmed plane and read scales for ``x @ w``:
    (v, g, i_max, decode)."""
    x = jnp.asarray(x, jnp.float32)
    w = jnp.asarray(w, jnp.float32)
    k = w.shape[0]
    w_scale = jnp.max(jnp.abs(w))
    w_scale = jnp.where(w_scale == 0.0, 1.0, w_scale)
    wn = w / w_scale
    g_ap, g_fs = a["g_ap"], a["g_fs"]
    gp = g_ap + jnp.maximum(wn, 0.0) * g_fs
    gn = g_ap + jnp.maximum(-wn, 0.0) * g_fs
    r_line = a["r_wire"] * k / 2.0
    att_p = 1.0 / (1.0 + r_line * jnp.sum(gp, axis=0))
    att_n = 1.0 / (1.0 + r_line * jnp.sum(gn, axis=0))
    att_mean = 0.5 * (jnp.mean(att_p) + jnp.mean(att_n))
    x_scale = jnp.max(jnp.abs(x))
    x_scale = jnp.where(x_scale == 0.0, 1.0, x_scale)
    v = a["v_read"] * x / x_scale
    g = att_p[None, :] * gp - att_n[None, :] * gn
    i_sigma = (jnp.sqrt(jnp.mean(v * v)) * jnp.sqrt(jnp.mean(g * g))
               * math.sqrt(k))
    i_max = _round_2sig(jnp.maximum(a["fs_sigmas"] * i_sigma, 1e-30))
    dec = (x_scale * w_scale) / (a["v_read"] * g_fs * att_mean)
    return v, g, i_max, dec


def _split(x):
    """x = head + tail, each rounded to bfloat16's 8-bit mantissa
    (``reduce_precision`` is kept by XLA where a pair of converts could be
    folded away)."""
    bf16 = dict(exponent_bits=8, mantissa_bits=7)
    hi = jax.lax.reduce_precision(x, **bf16)
    return hi, jax.lax.reduce_precision(x - hi, **bf16)


def matmul(spec: str, x, y, mode: str):
    """``einsum(spec, x, y)`` in float32 at ``mode``'s precision."""
    hp = jax.lax.Precision.HIGHEST
    if mode == "highest":
        return jnp.einsum(spec, x, y, precision=hp)
    assert mode == "high", mode
    if jax.default_backend() == "tpu":
        return jnp.einsum(spec, x, y, precision=jax.lax.Precision.HIGH)
    (xh, xl), (yh, yl) = _split(x), _split(y)
    return (jnp.einsum(spec, xl, yh, precision=hp)
            + jnp.einsum(spec, xh, yl, precision=hp)
            + jnp.einsum(spec, xh, yh, precision=hp))


def read(v, g, i_max, dec, a: dict, mode: str):
    """ADC read of the crossbar for the rows of ``v`` (``adc_bits`` 0: an
    ideal converter, no rounding)."""
    i = matmul("mk,kn->mn", v, g, mode)
    if a["adc_bits"] <= 0:
        return i * dec
    half = float(2 ** (a["adc_bits"] - 1) - 1)
    q = jnp.round(jnp.clip(i / i_max, -1.0, 1.0) * half) / half * i_max
    return q * dec


def analog_linear(x, w, a: dict, mode):
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    v, g, i_max, dec = prepare(x2, w, a)
    return read(v, g, i_max, dec, a, mode).reshape(*lead, w.shape[-1])


def rms_norm(x, scale, eps):
    dt = x.dtype
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dt)


def rope(x, positions, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def block(x, p, m: dict, a: dict, mode):
    """One decoder layer: attention then gated FFN, pre-norm residuals."""
    b, s, _ = x.shape
    h_, kv, hd = m["n_heads"], m["n_kv_heads"], m["d_head"]
    lin = functools.partial(analog_linear, a=a, mode=mode)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    h = rms_norm(x, p["ln1"], m["norm_eps"])
    at = p["attn"]
    q = lin(h, at["wq"].astype(h.dtype)) + at["bq"].astype(h.dtype)
    k = lin(h, at["wk"].astype(h.dtype)) + at["bk"].astype(h.dtype)
    v = lin(h, at["wv"].astype(h.dtype)) + at["bv"].astype(h.dtype)
    q = rope(q.reshape(b, s, h_, hd), pos, m["rope_theta"])
    k = rope(k.reshape(b, s, kv, hd), pos, m["rope_theta"])
    v = v.reshape(b, s, kv, hd)
    qg = q.reshape(b, s, kv, h_ // kv, hd)
    sc = matmul("bskgd,btkd->bkgst", qg, k, mode).astype(jnp.float32)
    sc = sc / jnp.sqrt(hd).astype(jnp.float32)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    sc = sc + jnp.where(causal, 0.0, -1e30).astype(jnp.float32)
    wts = jax.nn.softmax(sc, axis=-1).astype(q.dtype)
    o = matmul("bkgst,btkd->bskgd", wts, v, mode)
    o = o.reshape(b, s, h_ * hd)
    x = x + lin(o, at["wo"].astype(o.dtype))

    h = rms_norm(x, p["ln2"], m["norm_eps"])
    f = p["ffn"]
    gate = jax.nn.silu(lin(h, f["w_gate"].astype(h.dtype)))
    up = lin(h, f["w_up"].astype(h.dtype))
    return x + lin(gate * up, f["w_down"].astype(h.dtype))


@functools.partial(jax.jit, static_argnames=("m", "a", "mode"))
def _embed(embed, tokens, *, m, a, mode):
    d = m["d_model"]
    return (jnp.take(embed, tokens, axis=0).astype(m["compute_dtype"])
            * jnp.sqrt(float(d)))


@functools.partial(jax.jit, static_argnames=("m", "a", "mode"))
def _block(x, p, *, m, a, mode):
    return block(x, p, m, a, mode)


@functools.partial(jax.jit, static_argnames=("m", "a", "mode"))
def _head_prep(x, final_norm, embed, *, m, a, mode):
    h = rms_norm(x, final_norm, m["norm_eps"])
    h2 = h.reshape(-1, h.shape[-1])
    return prepare(h2, embed.T.astype(h2.dtype), a)


@functools.partial(jax.jit, static_argnames=("a", "mode"))
def _head_read(v_rows, g, i_max, dec, *, a, mode):
    return read(v_rows, g, i_max, dec, a, mode)


class Frozen(dict):
    """A hashable dict of static numbers (jit static arguments)."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def final_hidden(params, tokens, m: Frozen, a: Frozen, mode: str):
    """Residual stream after the last layer, layer by layer."""
    kw = dict(m=m, a=a, mode=mode)
    x = _embed(params["embed"], tokens, **kw)
    blocks = params["blocks"]["pos0"]
    for layer in range(m["n_layers"]):
        x = _block(x, jax.tree_util.tree_map(lambda t: t[layer], blocks),
                   **kw)
    return x


def head(params, x, m: Frozen, a: Frozen, mode: str):
    """``rows(lo, hi)``: logits of positions lo:hi, the output head
    prepared over all positions."""
    v, g, i_max, dec = _head_prep(x, params["final_norm"], params["embed"],
                                  m=m, a=a, mode=mode)

    def rows(lo, hi):
        return _head_read(v[lo:hi], g, i_max, dec, a=a, mode=mode)

    return rows

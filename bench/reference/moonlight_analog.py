"""Plain reference of a DeepSeek-V3-style decoder forward (Moonlight-16B-A3B:
latent attention, a leading dense layer, then sigmoid-routed experts with
shared experts), every linear a differential-conductance crossbar read by an
ADC, on one chip's expert-parallel share.

Written from the published architecture (DeepSeek-V3, arXiv:2412.19437,
with the sizes of https://huggingface.co/moonshotai/Moonlight-16B-A3B) and
the analog read model of ``qwen2_analog`` (whose crossbar ``prepare`` and
``read`` it uses), in ``jax.numpy`` and float32; it imports nothing of the
system under test.  Per layer, pre-norm residuals:

  attention (MLA, no query low-rank path): q = h Wq -> per head q_nope |
    q_pe;  h Wkv_a -> c_kv | k_pe (one rope key for all heads);
    k_nope | v = RMSNorm(c_kv) Wkv_b;  RoPE on q_pe and k_pe;
    softmax((q_nope|q_pe)(k_nope|k_pe)^T / sqrt(nope + rope)) v, then Wo
  layer 0: SwiGLU FFN of width ``d_ff``
  layers 1..: s = sigmoid(h W_r) over all routed experts; the top-k chosen
    on s + bias; gates = chosen s / their sum * routed_scale; each held
    expert e gathers its tokens and runs SwiGLU on its own crossbars (its
    own max|w|, max|x| over its tokens, IR planes), weighted by the gate;
    plus a shared SwiGLU of width ``d_shared``
  head: RMSNorm, untied output matrix

Departures from the published model:

* RoPE rotates contiguous halves of the rope columns (rotate-half); the
  published layout interleaves pairs.  With random weights the two are the
  same up to a fixed permutation of Wq's and Wkv_a's rope columns.
* RMSNorm scales are applied as ``1 + s`` (the program's parameterisation
  of the published ``w``, ``w = 1 + s``); weights are random from a seed.
* The router's selection bias, trained in the published model, is set
  here by ``balanced_bias``: even expert loads over token batches, as
  auxiliary-loss-free balancing leaves a trained model.
* One chip's share (model-configs guide, section 4): ``n_held`` of the
  routed experts, from ``held_first``; the router keeps all its outputs
  and its top-k over all of them, and the absent experts' part of each
  layer is left out, here as in the program.  Fewer layers and a slice of
  the vocabulary, as the configuration lists.
* The router is a crossbar read like every other linear (the published
  router is a digital float32 product).
* Routing ties: the check passes ``route_ids``, the program's chosen
  experts, and the reference takes them for a token only where its own
  choice differs and every expert in the difference lies within
  ``tie_margin`` of its own boundary between the k-th and (k+1)-th
  biased score (``route``); every other differing choice is counted.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.qwen2_analog import (Frozen, analog_linear, matmul,
                                          prepare, read, rms_norm, rope)

# rows of an expert's token block are padded to this multiple, so that
# experts of similar load share one compiled program; zero rows change
# only the rms behind the ADC full scale, which an ideal converter never
# reads, so padding is used only with ``adc_bits`` 0
ROW_BUCKET = 128


def _lin(x, w, a, mode):
    return analog_linear(x, w.astype(x.dtype), a, mode)


def mla(x, p, m: dict, a: dict, mode):
    """Latent attention of one layer on the normed input ``x``."""
    b, s, _ = x.shape
    h_, nope, rp = m["n_heads"], m["qk_nope"], m["qk_rope"]
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    q = _lin(x, p["wq"], a, mode).reshape(b, s, h_, nope + rp)
    kv = _lin(x, p["wkv_a"], a, mode)
    c_kv = rms_norm(kv[..., :m["kv_lora"]], p["kv_norm"], m["norm_eps"])
    k_pe = rope(kv[..., m["kv_lora"]:][:, :, None, :], pos, m["rope_theta"])
    kvb = _lin(c_kv, p["wkv_b"], a, mode).reshape(b, s, h_, -1)
    q = jnp.concatenate([q[..., :nope],
                         rope(q[..., nope:], pos, m["rope_theta"])], -1)
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(k_pe, (b, s, h_, rp))], -1)
    v = kvb[..., nope:]
    sc = matmul("bshd,bthd->bhst", q, k, mode).astype(jnp.float32)
    sc = sc / jnp.sqrt(nope + rp).astype(jnp.float32)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    sc = sc + jnp.where(causal, 0.0, -1e30).astype(jnp.float32)
    wts = jax.nn.softmax(sc, axis=-1).astype(q.dtype)
    o = matmul("bhst,bthd->bshd", wts, v, mode).reshape(b, s, -1)
    return _lin(o, p["wo"], a, mode)


def swiglu(x, p, a: dict, mode):
    gate = jax.nn.silu(_lin(x, p["w_gate"], a, mode))
    return _lin(gate * _lin(x, p["w_up"], a, mode), p["w_down"], a, mode)


@functools.partial(jax.jit, static_argnames=("m", "a", "mode"))
def _embed(embed, tokens, *, m, a, mode):
    return jnp.take(embed, tokens, axis=0).astype(m["compute_dtype"])


@functools.partial(jax.jit, static_argnames=("m", "a", "mode"))
def _dense_block(x, p, *, m, a, mode):
    x = x + mla(rms_norm(x, p["ln1"], m["norm_eps"]), p["attn"], m, a, mode)
    return x + swiglu(rms_norm(x, p["ln2"], m["norm_eps"]), p["ffn"], a, mode)


def _router_shared(h2, f, a, mode):
    """The router's sigmoid scores (tokens, experts) and the shared
    experts' output for the FFN input rows ``h2``."""
    s = jax.nn.sigmoid(_lin(h2.astype(jnp.float32), f["router"], a, mode))
    return s, swiglu(h2, f["shared"], a, mode)


@functools.partial(jax.jit, static_argnames=("m", "a", "mode"))
def _moe_front(x, p, *, m, a, mode):
    """Attention half of an expert layer, then the FFN input rows, the
    router's scores and the shared experts' output."""
    x = x + mla(rms_norm(x, p["ln1"], m["norm_eps"]), p["attn"], m, a, mode)
    h = rms_norm(x, p["ln2"], m["norm_eps"])
    h2 = h.reshape(-1, h.shape[-1])
    return (x, h2) + _router_shared(h2, p["ffn"], a, mode)


@functools.partial(jax.jit, static_argnames=("a", "mode"))
def _expert(y, h2, tok, gate, n, w_gate, w_up, w_down, *, a, mode):
    """``y`` plus one expert's gated output on its tokens: ``tok`` and
    ``gate`` hold its first ``n`` rows, the rest is padding (zero rows,
    gate 0)."""
    valid = (jnp.arange(tok.shape[0]) < n)[:, None]
    rows = jnp.where(valid, h2[tok], 0.0)
    out = swiglu(rows, {"w_gate": w_gate, "w_up": w_up, "w_down": w_down},
                 a, mode)
    return y.at[tok].add(out * gate[:, None])


@functools.partial(jax.jit, static_argnames=("m", "a", "mode"))
def _head_prep(x, final_norm, unembed, *, m, a, mode):
    h = rms_norm(x, final_norm, m["norm_eps"])
    h2 = h.reshape(-1, h.shape[-1])
    return prepare(h2, unembed.astype(h2.dtype), a)


@functools.partial(jax.jit, static_argnames=("a", "mode"))
def _head_read(v_rows, g, i_max, dec, *, a, mode):
    return read(v_rows, g, i_max, dec, a, mode)


def route(s: np.ndarray, bias: np.ndarray, top_k: int, given=None,
          tie_margin: float = 0.0):
    """(ids (T, top_k), ties, mismatched) of the top-k of ``s + bias``.

    With ``given`` (the program's ids), a token whose own choice differs
    takes the given ids when every expert in the difference lies within
    ``tie_margin`` of the boundary between its k-th and (k+1)-th biased
    score (a tie: ``ties`` counts its differing choices); otherwise it
    keeps its own and its differing choices count in ``mismatched``."""
    b = s.astype(np.float64) + bias.astype(np.float64)
    order = np.argsort(-b, axis=-1, kind="stable")
    ids = order[:, :top_k]
    if given is None:
        return ids, 0, 0
    n_exp = s.shape[-1]
    mine = np.zeros(b.shape, bool)
    np.put_along_axis(mine, ids, True, axis=-1)
    theirs = np.zeros(b.shape, bool)
    np.put_along_axis(theirs, np.asarray(given), True, axis=-1)
    differ = mine != theirs
    kth = np.take_along_axis(b, order[:, top_k - 1:top_k + 1], axis=-1)
    edge = 0.5 * (kth[:, :1] + kth[:, 1:]) if top_k < n_exp else kth[:, :1]
    near = np.abs(b - edge) < tie_margin
    moved = (theirs & ~mine).sum(-1)
    tie = differ.any(-1) & np.all(near | ~differ, axis=-1)
    ids = np.where(tie[:, None], np.asarray(given), ids)
    return ids, int(moved[tie].sum()), int(moved[~tie].sum())


def _bucket(n: int, a: dict) -> int:
    if a["adc_bits"] > 0:
        return n
    return max(ROW_BUCKET, -(-n // ROW_BUCKET) * ROW_BUCKET)


def moe_ffn(h2, s, shared, f, ids, m: dict, a: dict, mode):
    """Routed experts held here, each over its own tokens, plus the
    shared experts' output: (tokens, d)."""
    s_np = np.asarray(s, np.float64)
    gates = np.take_along_axis(s_np, ids, axis=-1)
    gates = gates / gates.sum(-1, keepdims=True) * m["routed_scale"]
    y = shared
    lo = m["held_first"]
    for e in range(m["n_held"]):
        tok, slot = np.nonzero(ids == lo + e)
        if tok.size == 0:
            continue
        pad = _bucket(tok.size, a) - tok.size
        gate = np.pad(gates[tok, slot].astype(np.float32), (0, pad))
        y = _expert(y, h2, np.pad(tok, (0, pad)).astype(np.int32), gate,
                    tok.size, f["w_gate"][e], f["w_up"][e], f["w_down"][e],
                    a=a, mode=mode)
    return y


def final_hidden(params, tokens, m: Frozen, a: Frozen, mode: str,
                 route_ids=None, tie_margin: float = 0.0):
    """(residual stream after the last layer, routing readings).  The
    readings: ``route_ties`` and ``route_mismatch`` summed over the expert
    layers (``route``), and ``ids`` of each."""
    kw = dict(m=m, a=a, mode=mode)
    x = _embed(params["embed"], tokens, **kw)
    lead = params["lead"]["pos0"]
    for layer in range(m["first_k_dense"]):
        x = _dense_block(x, jax.tree_util.tree_map(lambda t: t[layer], lead),
                         **kw)
    blocks = params["blocks"]["pos0"]
    ties = mismatch = 0
    all_ids = []
    for layer in range(m["n_layers"] - m["first_k_dense"]):
        p = jax.tree_util.tree_map(lambda t: t[layer], blocks)
        x, h2, s, shared = _moe_front(x, p, **kw)
        given = None if route_ids is None else np.asarray(route_ids[layer])
        ids, t, mm = route(np.asarray(s), np.asarray(p["ffn"]["router_bias"]),
                           m["top_k"], given, tie_margin)
        ties, mismatch = ties + t, mismatch + mm
        all_ids.append(ids)
        y = moe_ffn(h2, s, shared, p["ffn"], ids, m, a, mode)
        x = x + y.reshape(x.shape).astype(x.dtype)
    return x, {"route_ties": ties, "route_mismatch": mismatch,
               "ids": np.stack(all_ids) if all_ids else None}


BALANCE_STEPS = 50     # bias updates on each layer's fixed scores
BALANCE_GAIN = 0.1     # bias moved per unit of relative load, over sqrt(step)


@functools.partial(jax.jit, static_argnames=("top_k",))
def _balance(s, bias, *, top_k):
    """``bias`` moved against each expert's load of the top-k of ``s +
    bias`` (tokens, experts) until the loads are even."""
    n = s.shape[-1]

    def step(t, b):
        _, ids = jax.lax.top_k(s + b, top_k)
        load = jnp.zeros((n,), jnp.float32).at[ids.reshape(-1)].add(1.0)
        gain = BALANCE_GAIN / jnp.sqrt(t + 1.0)
        return b - gain * (load / jnp.mean(load) - 1.0)

    return jax.lax.fori_loop(0, BALANCE_STEPS, step,
                             bias.astype(jnp.float32))


def balanced_bias(params, batches, m: Frozen, a: Frozen, mode: str):
    """Selection bias (expert layers, routed experts) under which the
    routed experts' loads come out even over the token ``batches``, as
    auxiliary-loss-free balancing leaves a trained model.  Layer by layer:
    the layer's router scores over all batches, its bias (from the one in
    ``params``) moved until the loads are even, then the batches routed
    with it into the next layer."""
    kw = dict(m=m, a=a, mode=mode)
    xs = [_embed(params["embed"], t, **kw) for t in batches]
    lead = params["lead"]["pos0"]
    for layer in range(m["first_k_dense"]):
        p = jax.tree_util.tree_map(lambda t: t[layer], lead)
        xs = [_dense_block(x, p, **kw) for x in xs]
    blocks = params["blocks"]["pos0"]
    n_moe = m["n_layers"] - m["first_k_dense"]
    out = []
    for layer in range(n_moe):
        p = jax.tree_util.tree_map(lambda t: t[layer], blocks)
        fronts = [_moe_front(x, p, **kw) for x in xs]
        bias = np.asarray(_balance(jnp.concatenate([f[2] for f in fronts]),
                                   p["ffn"]["router_bias"], top_k=m["top_k"]))
        out.append(bias)
        if layer + 1 < n_moe:
            xs = [x + moe_ffn(h2, s, shared, p["ffn"],
                              route(np.asarray(s), bias, m["top_k"])[0],
                              m, a, mode).reshape(x.shape).astype(x.dtype)
                  for x, h2, s, shared in fronts]
    return np.stack(out)


def head(params, x, m: Frozen, a: Frozen, mode: str):
    """``rows(lo, hi)``: logits of positions lo:hi, the output head
    prepared over all positions."""
    v, g, i_max, dec = _head_prep(x, params["final_norm"], params["unembed"],
                                  m=m, a=a, mode=mode)

    def rows(lo, hi):
        return _head_read(v[lo:hi], g, i_max, dec, a=a, mode=mode)

    return rows


def moe_layer(params_layer, h2, m: Frozen, a: Frozen, mode: str):
    """One expert layer's FFN on its normed input rows ``h2`` (tokens, d),
    routed by the reference itself: (routed part held here, shared part)."""
    f = params_layer["ffn"]
    s, shared = jax.jit(_router_shared, static_argnums=(2, 3))(h2, f, a, mode)
    ids, _, _ = route(np.asarray(s), np.asarray(f["router_bias"]),
                      m["top_k"])
    routed = moe_ffn(h2, s, jnp.zeros_like(shared), f, ids, m, a, mode)
    return routed, shared

"""Feed-forward layers: gated dense (SwiGLU/GeGLU) and Mixture-of-Experts.

MoE has two dispatches over one router (``route``: softmax top-k, or
DeepSeek-V3's sigmoid top-k with a selection bias; DESIGN.md §12):

* GShard-style grouped dispatch with capacity factor (training and
  serving): tokens are grouped, each group computes a (Tg, E, C) one-hot
  combine tensor via a position-in-expert cumsum, and dispatch/return are
  einsums that GSPMD turns into all-to-alls when experts are sharded over
  the `model` mesh axis (expert parallelism).  Static shapes; tokens past
  an expert's capacity are dropped.  Aux losses (Switch load-balance +
  router z-loss) are returned to the training loss.
* Dropless sorted dispatch (full-sequence evaluation, and always under an
  interception hook): every (token, choice) pair routed to a held expert
  is sorted by expert and the expert products run as ``grouped_linear``
  over the sorted rows, so the analog forward maps each expert onto its
  own crossbar and no token is dropped.

``MoEConfig.held`` gives the contiguous range of routed experts this chip
holds (expert parallelism): the router scores every expert, and the pairs
routed to experts held elsewhere are left out.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.common import ParamSpec, act_fn, grouped_linear, linear

MOE_GROUP = 1024          # tokens per dispatch group
CAPACITY_FACTOR = 1.25


def dense_ffn_specs(cfg: ArchConfig) -> Dict[str, ParamSpec]:
    d, f = cfg.d_model, cfg.d_ff
    return {
        "w_gate": ParamSpec((d, f), ("embed", "ffn")),
        "w_up": ParamSpec((d, f), ("embed", "ffn")),
        "w_down": ParamSpec((f, d), ("ffn", "embed")),
    }


def dense_ffn(p, x, cfg: ArchConfig, prefix: str = ""):
    """Gated FFN; ``prefix`` goes before each site tag (``shared_w_up``)."""
    g = act_fn(linear(x, p["w_gate"].astype(x.dtype), prefix + "w_gate"),
               cfg.act)
    u = linear(x, p["w_up"].astype(x.dtype), prefix + "w_up")
    return linear(g * u, p["w_down"].astype(x.dtype), prefix + "w_down")


def moe_specs(cfg: ArchConfig) -> Dict[str, ParamSpec]:
    assert cfg.moe is not None
    import os

    d, e, f = cfg.d_model, cfg.moe.num_experts, cfg.moe.d_expert
    n_held = cfg.moe.held_range[1]
    # REPRO_MOE_2D: shard the expert hidden dim over `data` instead of
    # ZeRO-gathering expert weights (perf knob; EXPERIMENTS.md §Perf).
    fax = "expert_ffn" if os.environ.get("REPRO_MOE_2D") else "ffn"
    emb = None if os.environ.get("REPRO_MOE_2D") else "embed"
    sp = {
        "router": ParamSpec((d, e), ("embed", "experts"), dtype="float32"),
        "w_gate": ParamSpec((n_held, d, f), ("experts", emb, fax)),
        "w_up": ParamSpec((n_held, d, f), ("experts", emb, fax)),
        "w_down": ParamSpec((n_held, f, d), ("experts", fax, emb)),
    }
    if cfg.moe.score == "sigmoid":
        # selection-only bias (DeepSeek-V3 e_score_correction_bias)
        sp["router_bias"] = ParamSpec((e,), ("experts",), "zeros",
                                      dtype="float32")
    if cfg.moe.shared_expert:
        fs = cfg.moe.shared_width
        sp["shared"] = {
            "w_gate": ParamSpec((d, fs), ("embed", "ffn")),
            "w_up": ParamSpec((d, fs), ("embed", "ffn")),
            "w_down": ParamSpec((fs, d), ("ffn", "embed")),
        }
    return sp


def route(p, x, cfg: ArchConfig):
    """Router over all ``num_experts``: (gates, ids, probs, logits), gates
    and ids (..., top_k), probs and logits (..., E).

    softmax: the top-k softmax probabilities, renormalised.  sigmoid
    (DeepSeek-V3 noaux_tc, one group): ``s = sigmoid(logits)``, the top-k
    chosen on ``s + router_bias`` (the bias selects, it never weights),
    gates the chosen ``s`` renormalised.  Both scale by ``routed_scale``;
    ``probs`` (summing to one per token) feed the load-balance loss."""
    moe = cfg.moe
    logits = linear(x.astype(jnp.float32), p["router"],
                    "router").astype(jnp.float32)
    if moe.score == "sigmoid":
        s = jax.nn.sigmoid(logits)
        _, ids = jax.lax.top_k(s + p["router_bias"], moe.top_k)
        gates = jnp.take_along_axis(s, ids, axis=-1)
        probs = s / jnp.sum(s, axis=-1, keepdims=True)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gates, ids = jax.lax.top_k(probs, moe.top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    if moe.routed_scale != 1.0:
        gates = gates * moe.routed_scale
    return gates, ids, probs, logits


def moe_ffn_dropless(p, x, cfg: ArchConfig, routes=None) -> jnp.ndarray:
    """Dropless MoE layer over the held experts.  x: (B, S, d).

    The (token, choice) pairs routed to held experts are sorted by expert
    (stable, so each expert's rows keep token order) and run through
    ``grouped_linear``; pairs routed elsewhere are left out.  ``routes``,
    if a list, gets ``{"ids": (T, top_k) expert ids over all experts,
    "sizes": (n_held,) rows per held expert}``."""
    B, S, d = x.shape
    moe = cfg.moe
    k = moe.top_k
    lo, n = moe.held_range
    xt = x.reshape(B * S, d)
    gates, ids, _, _ = route(p, xt, cfg)
    local = ids.reshape(-1) - lo
    held = (local >= 0) & (local < n)
    key = jnp.where(held, local, n)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.zeros((n + 1,), jnp.int32).at[key].add(1)[:n]
    rows = xt[order // k]
    h = act_fn(grouped_linear(rows, p["w_gate"].astype(x.dtype), sizes,
                              "moe_w_gate"), cfg.act)
    h = h * grouped_linear(rows, p["w_up"].astype(x.dtype), sizes, "moe_w_up")
    yr = grouped_linear(h, p["w_down"].astype(x.dtype), sizes, "moe_w_down")
    wts = jnp.where(held, gates.reshape(-1), 0.0)[order].astype(yr.dtype)
    # back to (token, choice) order by a gather, then sum the choices
    y = (yr * wts[:, None])[jnp.argsort(order)].reshape(B * S, k, d)
    y = jnp.sum(y, axis=1).reshape(B, S, d).astype(x.dtype)
    if routes is not None:
        routes.append({"ids": ids.astype(jnp.int32), "sizes": sizes})
    if moe.shared_expert:
        y = y + dense_ffn(p["shared"], x, cfg, "shared_")
    return y


def moe_ffn(p, x, cfg: ArchConfig, routes=None, dropless: bool = False
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (output, aux_loss).  x: (B, S, d).  GShard capacity dispatch,
    or the dropless one (aux 0) when asked for (the analog forward)."""
    if dropless:
        return (moe_ffn_dropless(p, x, cfg, routes),
                jnp.zeros((), jnp.float32))
    B, S, d = x.shape
    e = cfg.moe.num_experts
    k = cfg.moe.top_k
    tg = min(MOE_GROUP, B * S)
    assert (B * S) % tg == 0, (B, S, tg)
    G = (B * S) // tg
    if tg <= 64:
        cap = tg * k            # tiny groups (decode/smoke): fully dropless
    else:
        cap = max(4, int(tg * k * CAPACITY_FACTOR / e))

    xt = x.reshape(G, tg, d)
    # top-k selection -> per-token (expert, gate) pairs
    gate_vals, expert_idx, probs, logits = route(p, xt, cfg)           # (G,Tg,k)

    # position-in-expert via cumsum over the flattened (token, k) choices
    sel = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)              # (G,Tg,k,E)
    sel_flat = sel.reshape(G, tg * k, e)
    pos = jnp.cumsum(sel_flat, axis=1) - sel_flat                       # (G,Tg*k,E)
    pos = jnp.sum(pos * sel_flat, axis=-1).reshape(G, tg, k)            # (G,Tg,k)
    keep = pos < cap
    gate_vals = gate_vals * keep

    pos_oh = jax.nn.one_hot(pos, cap, dtype=jnp.float32) * keep[..., None]
    # combine[g,t,e,c] = gate for token t's slot c of expert e
    combine = jnp.einsum("gtke,gtkc->gtec", sel, pos_oh * gate_vals[..., None])
    lo, n_held = cfg.moe.held_range
    if n_held != e:
        combine = combine[:, :, lo:lo + n_held]   # experts held elsewhere
    dispatch = (combine > 0.0).astype(x.dtype)

    xe = jnp.einsum("gtec,gtd->egcd", dispatch, xt)                     # (E,G,C,d)
    h_g = act_fn(jnp.einsum("egcd,edf->egcf", xe, p["w_gate"].astype(x.dtype)), cfg.act)
    h_u = jnp.einsum("egcd,edf->egcf", xe, p["w_up"].astype(x.dtype))
    ye = jnp.einsum("egcf,efd->egcd", h_g * h_u, p["w_down"].astype(x.dtype))
    y = jnp.einsum("egcd,gtec->gtd", ye, combine.astype(x.dtype))
    y = y.reshape(B, S, d)

    # Switch load-balance loss + router z-loss
    me = jnp.mean(probs, axis=1)                                        # (G,E)
    ce = jnp.mean(sel.sum(axis=2), axis=1)                              # (G,E)
    lb = e * jnp.mean(jnp.sum(me * ce, axis=-1))
    zl = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    aux = 0.01 * lb + 0.001 * zl

    if cfg.moe.shared_expert:
        y = y + dense_ffn(p["shared"], x, cfg, "shared_")
    return y, aux

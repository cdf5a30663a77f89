"""Model assembly: pattern-scanned decoder stacks for all 10 architectures.

Layers are grouped by the arch's repeating *pattern* (e.g. gemma2 =
[local, global], jamba = [attn + 7x mamba]); parameters for each pattern
position are stacked with a leading ``layers`` axis and the stack is
traversed with ``lax.scan`` — compact HLO (compile time ~ pattern length,
not n_layers) and the natural place for scan-over-layers remat.

Public API (all pure functions of (params, cfg, ...)):
  param_specs / init_params / abstract_params / logical_axes
  forward_train   — logits-free CE loss via seq-chunked softmax
  serve_prefill   — full-sequence forward, returns last-token logits + cache
  serve_step      — one decode token with threaded cache
  init_cache      — decode-cache pytree (ShapeDtypeStruct-able)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models import ffn as ffn_mod
from repro.models import mla as mla_mod
from repro.models import ssm as ssm_mod
from repro.models.common import (
    ParamSpec,
    abstract_params as _abstract,
    init_params as _init,
    linear,
    logical_axes as _axes,
    rms_norm,
    softcap,
)
from repro.models.sharding_hooks import constrain

LOSS_CHUNK = 1024


# --------------------------------------------------------------------------
# specs
# --------------------------------------------------------------------------
def _block_specs(cfg: ArchConfig, mixer: str, ffn: str, cross: bool) -> Dict[str, Any]:
    sp: Dict[str, Any] = {"ln1": ParamSpec((cfg.d_model,), ("embed",), "zeros")}
    if mixer.startswith("attn"):
        sp["attn"] = attn.attn_specs(cfg)
    elif mixer == "mla":
        sp["attn"] = mla_mod.mla_specs(cfg)
    elif mixer == "mamba":
        sp["mamba"] = ssm_mod.mamba_specs(cfg)
    else:
        raise ValueError(mixer)
    if cfg.post_norms:
        sp["post_ln1"] = ParamSpec((cfg.d_model,), ("embed",), "zeros")
    if cross:
        sp["ln_cross"] = ParamSpec((cfg.d_model,), ("embed",), "zeros")
        sp["cross"] = attn.attn_specs(cfg, cross=True)
    if ffn != "none":
        sp["ln2"] = ParamSpec((cfg.d_model,), ("embed",), "zeros")
        sp["ffn"] = ffn_mod.moe_specs(cfg) if ffn == "moe" else ffn_mod.dense_ffn_specs(cfg)
        if cfg.post_norms:
            sp["post_ln2"] = ParamSpec((cfg.d_model,), ("embed",), "zeros")
    return sp


def _stack_specs(specs: Any, n: int) -> Any:
    """Add a leading stacked-layers axis to every ParamSpec."""
    return jax.tree_util.tree_map(
        lambda s: ParamSpec((n,) + s.shape, ("layers",) + s.axes, s.init, s.dtype),
        specs,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


def param_specs(cfg: ArchConfig) -> Dict[str, Any]:
    n_rep = cfg.n_pattern_repeats
    specs: Dict[str, Any] = {
        "embed": ParamSpec((cfg.vocab, cfg.d_model), ("vocab", "embed"), "embed"),
        "final_norm": ParamSpec((cfg.d_model,), ("embed",), "zeros"),
    }
    if not cfg.tie_embeddings:
        specs["unembed"] = ParamSpec((cfg.d_model, cfg.vocab), ("embed", "vocab"))
    cross = cfg.n_encoder_layers > 0
    blocks = {}
    for i, (mixer, f) in enumerate(cfg.pattern):
        blocks[f"pos{i}"] = _stack_specs(_block_specs(cfg, mixer, f, cross), n_rep)
    specs["blocks"] = blocks
    if cfg.first_k_dense:
        # leading dense layers: their own stack, run before ``blocks``
        assert not cross, "leading dense layers are decoder-only"
        specs["lead"] = {
            f"pos{i}": _stack_specs(_block_specs(cfg, mixer, f, cross),
                                    cfg.first_k_dense)
            for i, (mixer, f) in enumerate(cfg.lead_pattern)}
    if cross:
        enc_cfg = cfg
        enc = _stack_specs(_block_specs(enc_cfg, "attn", "dense", False),
                           cfg.n_encoder_layers)
        specs["encoder"] = {"blocks": enc,
                            "final_norm": ParamSpec((cfg.d_model,), ("embed",), "zeros")}
    return specs


def init_params(cfg: ArchConfig, key: jax.Array):
    return _init(param_specs(cfg), cfg, key)


def abstract_params(cfg: ArchConfig):
    return _abstract(param_specs(cfg), cfg)


def logical_axes(cfg: ArchConfig):
    return _axes(param_specs(cfg))


# --------------------------------------------------------------------------
# blocks
# --------------------------------------------------------------------------
def _maybe_post(p, name, y, cfg):
    if cfg.post_norms:
        return rms_norm(y, p[name], cfg.norm_eps)
    return y


def _run_block(
    p,
    x,
    cfg: ArchConfig,
    mixer: str,
    ffn: str,
    positions,
    mem_kv=None,
    routes=None,
    dropless: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence block (train/prefill).  Returns (x, aux_loss).
    ``routes``/``dropless`` go to the MoE layer (``ffn.moe_ffn``)."""
    aux = jnp.zeros((), jnp.float32)
    # named scopes put the sub-layer into every device op's ``op_name``
    scope = "attn" if mixer.startswith("attn") else (
        "mla" if mixer == "mla" else "ssm")
    with jax.named_scope(scope):
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        if mixer.startswith("attn"):
            y = attn.self_attention(p["attn"], h, cfg, positions, mixer)
        elif mixer == "mla":
            y = mla_mod.mla_attention(p["attn"], h, cfg, positions)
        else:
            y = ssm_mod.mamba_forward(p["mamba"], h, cfg)
        x = x + _maybe_post(p, "post_ln1", y, cfg)
        x = constrain(x, "act_btd")
    if mem_kv is not None:
        h = rms_norm(x, p["ln_cross"], cfg.norm_eps)
        x = x + attn.cross_attention(p["cross"], h, mem_kv[0], mem_kv[1], cfg)
    if ffn != "none":
        with jax.named_scope("moe" if ffn == "moe" else "ffn"):
            h = rms_norm(x, p["ln2"], cfg.norm_eps)
            if ffn == "moe":
                y, a = ffn_mod.moe_ffn(p["ffn"], h, cfg, routes, dropless)
                aux = aux + a
            else:
                y = ffn_mod.dense_ffn(p["ffn"], h, cfg)
            x = x + _maybe_post(p, "post_ln2", y, cfg)
            x = constrain(x, "act_btd")
    return x, aux


def _scan_pattern(params_blocks, x, cfg: ArchConfig, positions, mem_kv=None,
                  remat: bool = True, pattern=None):
    """Scan the repeating pattern (default ``cfg.pattern``) over its stacked
    parameters."""
    aux_total = jnp.zeros((), jnp.float32)
    pattern = cfg.pattern if pattern is None else pattern

    def body(carry, layer_params):
        x, aux = carry
        for i, (mixer, f) in enumerate(pattern):
            x, a = _run_block(layer_params[f"pos{i}"], x, cfg, mixer, f,
                              positions, mem_kv)
            aux = aux + a
        return (x, aux), None

    body_fn = jax.checkpoint(body) if remat else body
    (x, aux_total), _ = jax.lax.scan(body_fn, (x, aux_total), params_blocks)
    return x, aux_total


# --------------------------------------------------------------------------
# embedding / heads
# --------------------------------------------------------------------------
def _embed(params, cfg: ArchConfig, tokens, frontend_embeds=None):
    dt = jnp.dtype(cfg.compute_dtype)
    e = params["embed"]
    x = jnp.take(e, tokens, axis=0).astype(dt)
    if cfg.scale_embed:
        x = x * jnp.sqrt(float(cfg.d_model))
    if frontend_embeds is not None:
        x = jnp.concatenate([frontend_embeds.astype(dt), x], axis=1)
    return constrain(x, "act_btd")


def _unembed_matrix(params, cfg: ArchConfig):
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["unembed"]


def _logits(params, cfg: ArchConfig, h):
    w = _unembed_matrix(params, cfg)
    logits = linear(h, w.astype(h.dtype), "unembed")
    logits = softcap(logits.astype(jnp.float32), cfg.final_softcap)
    return constrain(logits, "logits")


# --------------------------------------------------------------------------
# encoder (enc-dec archs)
# --------------------------------------------------------------------------
def _encode(params, cfg: ArchConfig, frame_embeds):
    dt = jnp.dtype(cfg.compute_dtype)
    x = constrain(frame_embeds.astype(dt), "act_btd")
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    def body(carry, layer_params):
        x, = carry
        h = rms_norm(x, layer_params["ln1"], cfg.norm_eps)
        x = x + attn.encoder_attention(layer_params["attn"], h, cfg, positions)
        h = rms_norm(x, layer_params["ln2"], cfg.norm_eps)
        x = x + ffn_mod.dense_ffn(layer_params["ffn"], h, cfg)
        return (constrain(x, "act_btd"),), None

    (x,), _ = jax.lax.scan(jax.checkpoint(body), (x,), params["encoder"]["blocks"])
    return rms_norm(x, params["encoder"]["final_norm"], cfg.norm_eps)


def _cross_kv(params, cfg: ArchConfig, enc_out):
    """Per-pattern-position stacked cross K/V from the encoder output."""
    out = {}
    for i in range(len(cfg.pattern)):
        blk = params["blocks"][f"pos{i}"]["cross"]
        k, v = jax.vmap(
            lambda wk, wv: attn.project_memory_kv({"wk": wk, "wv": wv}, enc_out, cfg)
        )(blk["wk"], blk["wv"])
        out[f"pos{i}"] = (k, v)
    return out


# --------------------------------------------------------------------------
# training forward (chunked CE loss; no [B,S,V] materialization)
# --------------------------------------------------------------------------
def forward_train(params, cfg: ArchConfig, batch: Dict[str, jnp.ndarray]):
    """Returns (loss, metrics).  batch: tokens (B,S), labels (B,S) [-1 = pad],
    optional frontend_embeds (B,F,d) / encoder frames."""
    tokens = batch["tokens"]
    labels = batch["labels"]
    fe = batch.get("frontend_embeds")
    mem_kv = None
    if cfg.n_encoder_layers:
        enc_out = _encode(params, cfg, batch["encoder_frames"])
        # cross K/V are shared across scanned layers per pattern position
        mem_kv = None  # computed inside block scan via stacked params
        x = _embed(params, cfg, tokens)
    else:
        x = _embed(params, cfg, tokens, fe)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    if cfg.n_encoder_layers:
        # Simpler faithful path: scan with cross-attn recomputing K/V per
        # layer from enc_out (cheap relative to decoder self-attn at S=4k).
        aux_total = jnp.zeros((), jnp.float32)

        def body(carry, layer_params):
            x, aux = carry
            for i, (mixer, f) in enumerate(cfg.pattern):
                lp = layer_params[f"pos{i}"]
                kv = attn.project_memory_kv(lp["cross"], enc_out, cfg)
                x, a = _run_block(lp, x, cfg, mixer, f, positions, kv)
                aux = aux + a
            return (x, aux), None

        (x, aux), _ = jax.lax.scan(jax.checkpoint(body), (x, aux_total),
                                   params["blocks"])
    else:
        aux = jnp.zeros((), jnp.float32)
        if cfg.first_k_dense:
            x, aux = _scan_pattern(params["lead"], x, cfg, positions,
                                   pattern=cfg.lead_pattern)
        x, a = _scan_pattern(params["blocks"], x, cfg, positions)
        aux = aux + a

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)

    # strip frontend positions from the loss (labels cover text tokens only)
    if fe is not None:
        x = x[:, fe.shape[1]:]

    w = _unembed_matrix(params, cfg)
    S_txt = x.shape[1]
    n_chunks = max(1, S_txt // LOSS_CHUNK)
    xc = x.reshape(B, n_chunks, S_txt // n_chunks, -1).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, n_chunks, S_txt // n_chunks).transpose(1, 0, 2)

    def ce_chunk(carry, xs_):
        h, lab = xs_
        logits = softcap((h @ w.astype(h.dtype)).astype(jnp.float32),
                         cfg.final_softcap)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(lab, 0)[..., None], axis=-1
        )[..., 0]
        valid = (lab >= 0).astype(jnp.float32)
        ce = jnp.sum((logz - gold) * valid)
        return carry + jnp.stack([ce, jnp.sum(valid)]), None

    # checkpoint: recompute the chunk logits in the backward pass instead of
    # saving [B, chunk, V]-sized softmax residuals for every chunk
    totals, _ = jax.lax.scan(jax.checkpoint(ce_chunk), jnp.zeros(2), (xc, lc))
    loss = totals[0] / jnp.maximum(totals[1], 1.0) + aux
    return loss, {"ce": totals[0] / jnp.maximum(totals[1], 1.0), "aux": aux,
                  "tokens": totals[1]}


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------
def _mixer_cache(cfg: ArchConfig, mixer: str, batch: int, max_seq: int, dt):
    if mixer.startswith("attn"):
        return attn.init_kv_cache(cfg, batch, max_seq, dt)
    if mixer == "mla":
        return mla_mod.init_mla_cache(cfg, batch, max_seq, dt)
    return ssm_mod.init_mamba_cache(cfg, batch, dt)


def _stacked_cache(cfg: ArchConfig, pattern, n: int, batch: int,
                   max_seq: int, dt):
    return {f"pos{i}": jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (n,) + a.shape),
        _mixer_cache(cfg, mixer, batch, max_seq, dt))
        for i, (mixer, _) in enumerate(pattern)}


def init_cache(cfg: ArchConfig, batch: int, max_seq: int) -> Dict[str, Any]:
    dt = jnp.dtype(cfg.compute_dtype)
    n_rep = cfg.n_pattern_repeats
    cache: Dict[str, Any] = {
        "pos": jnp.zeros((), jnp.int32),
        "blocks": _stacked_cache(cfg, cfg.pattern, n_rep, batch, max_seq, dt)}
    if cfg.first_k_dense:
        cache["lead"] = _stacked_cache(cfg, cfg.lead_pattern,
                                       cfg.first_k_dense, batch, max_seq, dt)
    if cfg.n_encoder_layers:
        kv, hd = cfg.n_kv_heads, cfg.d_head
        cache["cross"] = {
            f"pos{i}": (
                jnp.zeros((n_rep, batch, cfg.frontend_positions, kv, hd), dt),
                jnp.zeros((n_rep, batch, cfg.frontend_positions, kv, hd), dt),
            )
            for i in range(len(cfg.pattern))
        }
    return cache


def serve_prefill(params, cfg: ArchConfig, batch: Dict[str, jnp.ndarray],
                  max_seq: int):
    """Prefill: full forward; returns (last_logits, populated cache)."""
    tokens = batch["tokens"]
    fe = batch.get("frontend_embeds")
    x = _embed(params, cfg, tokens, fe)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    cache = init_cache(cfg, B, max_seq)

    enc_out = None
    if cfg.n_encoder_layers:
        enc_out = _encode(params, cfg, batch["encoder_frames"])

    aux0 = jnp.zeros((), jnp.float32)

    def body(carry, layer_params, pattern):
        x, aux = carry
        ys = {}
        for i, (mixer, f) in enumerate(pattern):
            lp = layer_params[f"pos{i}"]
            h = rms_norm(x, lp["ln1"], cfg.norm_eps)
            if mixer.startswith("attn") or mixer == "mla":
                if mixer == "mla":
                    q, k, v = mla_mod.project_qkv(lp["attn"], h, cfg,
                                                  positions)
                    merge = mla_mod.merge_heads
                else:
                    q, k, v = attn._project_qkv(lp["attn"], h, cfg, positions)
                    merge = attn._merge_heads
                fn = (attn.chunked_attention if S > attn.CHUNK_THRESHOLD
                      else attn.full_attention)
                window = cfg.attn.sliding_window if mixer == "attn_local" else None
                o = fn(q, k, v, cfg, causal=True, window=window)
                y = merge(lp["attn"], o, cfg)
                pad = max_seq - S
                ck = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
                cv = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
                ys[f"pos{i}"] = {"k": ck, "v": cv}
                x = x + _maybe_post(lp, "post_ln1", y, cfg)
            else:
                # prefill the mamba states by running the recurrence to S
                y = ssm_mod.mamba_forward(lp["mamba"], h, cfg)
                st = _mamba_state_after(lp["mamba"], h, cfg)
                ys[f"pos{i}"] = st
                x = x + _maybe_post(lp, "post_ln1", y, cfg)
            x = constrain(x, "act_btd")
            if enc_out is not None:
                hc = rms_norm(x, lp["ln_cross"], cfg.norm_eps)
                kc, vc = attn.project_memory_kv(lp["cross"], enc_out, cfg)
                x = x + attn.cross_attention(lp["cross"], hc, kc, vc, cfg)
                ys.setdefault("_cross", {})[f"pos{i}"] = (kc, vc)
            if f != "none":
                h = rms_norm(x, lp["ln2"], cfg.norm_eps)
                if f == "moe":
                    y, a = ffn_mod.moe_ffn(lp["ffn"], h, cfg)
                    aux = aux + a
                else:
                    y = ffn_mod.dense_ffn(lp["ffn"], h, cfg)
                x = x + _maybe_post(lp, "post_ln2", y, cfg)
                x = constrain(x, "act_btd")
        return (x, aux), ys

    if cfg.first_k_dense:
        (x, _), cache["lead"] = jax.lax.scan(
            functools.partial(body, pattern=cfg.lead_pattern), (x, aux0),
            params["lead"])
    (x, _), stacked = jax.lax.scan(
        functools.partial(body, pattern=cfg.pattern), (x, aux0),
        params["blocks"])

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _logits(params, cfg, x[:, -1:, :])

    cache["pos"] = jnp.asarray(S, jnp.int32)
    cross = stacked.pop("_cross", None)
    cache["blocks"] = stacked
    if cross is not None:
        cache["cross"] = cross
    return logits, cache


def _mamba_state_after(p, x, cfg: ArchConfig):
    """Final (conv, ssm) state after processing sequence x — decode handoff.
    Handles non-chunk-multiple L like mamba_forward (dt-masked padding)."""
    d_in, H, P, N, K = ssm_mod._dims(cfg)
    B, L_real, _ = x.shape
    Q = cfg.ssm.chunk
    pad = (-L_real) % Q
    xbc_raw = x @ p["w_xbc"].astype(x.dtype)
    conv_state = xbc_raw[:, L_real - (K - 1):L_real, :]
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    L = L_real + pad
    nC = L // Q
    xbc = x @ p["w_xbc"].astype(x.dtype)
    xbc_c = jax.nn.silu(ssm_mod._causal_conv(xbc, p["conv_w"].astype(x.dtype)))
    xs, Bs, Cs = jnp.split(xbc_c, [d_in, d_in + N], axis=-1)
    dt = jax.nn.softplus((x @ p["w_dt"].astype(x.dtype)).astype(jnp.float32)
                         + p["dt_bias"])
    if pad:
        valid = (jnp.arange(L) < L_real)[None, :, None]
        dt = dt * valid
    A = -jnp.exp(p["a_log"].astype(jnp.float32))
    xh = xs.reshape(B, nC, Q, H, P)
    Bc = Bs.reshape(B, nC, Q, N)
    dtc = dt.reshape(B, nC, Q, H)
    da = dtc * A
    seg = jnp.cumsum(da, axis=2)
    seg_last = seg[:, :, -1:, :]
    decay_out = jnp.exp(seg_last - seg)
    chunk_state = jnp.einsum(
        "bcqh,bcqn,bcqhp->bchpn", (decay_out * dtc).astype(jnp.float32),
        Bc.astype(jnp.float32), xh.astype(jnp.float32))
    chunk_decay = jnp.exp(seg_last[:, :, 0, :])

    def scan_body(s_prev, xs_):
        cs, cd = xs_
        return s_prev * cd[:, :, None, None] + cs, None

    s_final, _ = jax.lax.scan(
        scan_body, jnp.zeros((B, H, P, N), jnp.float32),
        (chunk_state.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)))
    return {"conv": conv_state, "ssm": s_final}


def serve_step(params, cfg: ArchConfig, cache: Dict[str, Any],
               tokens: jnp.ndarray):
    """One decode step.  tokens: (B, 1) int32.  Returns (logits, cache)."""
    x = _embed(params, cfg, tokens)
    pos = cache["pos"]
    aux0 = jnp.zeros((), jnp.float32)

    has_cross = "cross" in cache
    xs_in = (params["blocks"], cache["blocks"]) + (
        (cache["cross"],) if has_cross else ()
    )

    def body(carry, xs_, pattern, has_cross):
        x, = carry
        if has_cross:
            layer_params, layer_cache, layer_cross = xs_
        else:
            layer_params, layer_cache = xs_
            layer_cross = None
        new_cache = {}
        for i, (mixer, f) in enumerate(pattern):
            lp = layer_params[f"pos{i}"]
            lc = layer_cache[f"pos{i}"]
            h = rms_norm(x, lp["ln1"], cfg.norm_eps)
            if mixer.startswith("attn"):
                y, nc = attn.decode_self_attention(lp["attn"], h, lc, pos, cfg, mixer)
            elif mixer == "mla":
                y, nc = mla_mod.mla_decode(lp["attn"], h, lc, pos, cfg)
            else:
                y, nc = ssm_mod.mamba_decode_step(lp["mamba"], h, lc, cfg)
            new_cache[f"pos{i}"] = nc
            x = x + _maybe_post(lp, "post_ln1", y, cfg)
            if layer_cross is not None:
                hc = rms_norm(x, lp["ln_cross"], cfg.norm_eps)
                kc, vc = layer_cross[f"pos{i}"]
                x = x + attn.cross_attention(lp["cross"], hc, kc, vc, cfg)
            if f != "none":
                h = rms_norm(x, lp["ln2"], cfg.norm_eps)
                if f == "moe":
                    y, _ = ffn_mod.moe_ffn(lp["ffn"], h, cfg)
                else:
                    y = ffn_mod.dense_ffn(lp["ffn"], h, cfg)
                x = x + _maybe_post(lp, "post_ln2", y, cfg)
        return (x,), new_cache

    new_cache = dict(cache)
    if cfg.first_k_dense:
        (x,), new_cache["lead"] = jax.lax.scan(
            functools.partial(body, pattern=cfg.lead_pattern,
                              has_cross=False),
            (x,), (params["lead"], cache["lead"]))
    (x,), new_cache["blocks"] = jax.lax.scan(
        functools.partial(body, pattern=cfg.pattern, has_cross=has_cross),
        (x,), xs_in)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _logits(params, cfg, x)
    new_cache["pos"] = pos + 1
    return logits, new_cache

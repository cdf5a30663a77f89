"""Grouped-query attention: full/chunked (flash-style) + decode-with-cache.

Supports the pool's feature set: GQA (kv_heads <= heads), sliding-window
(local) layers, logit softcapping (gemma2), per-head q/k RMSNorm (qwen3),
QKV bias (qwen2), RoPE / M-RoPE (qwen2-vl), cross-attention (seamless).

The prefill path switches to a chunked online-softmax formulation
(``chunked_attention``) above ``CHUNK_THRESHOLD`` so 32k-token prefill never
materializes an S x S score matrix — lax.scan over KV chunks carrying
(m, l, acc), the standard flash recurrence, which GSPMD shards cleanly.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.common import ParamSpec, apply_rope, linear, rms_norm, softcap
from repro.models.sharding_hooks import constrain

# Above this query length the flash path is used even in training — a 4k x 4k
# fp32 score tensor per layer would blow HBM at production batch sizes.
CHUNK_THRESHOLD = 2048
KV_CHUNK = 1024


def attn_specs(cfg: ArchConfig, cross: bool = False) -> Dict[str, ParamSpec]:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    sp = {
        "wq": ParamSpec((d, h * hd), ("embed", "q_proj")),
        "wk": ParamSpec((d, kv * hd), ("embed", "kv_proj")),
        "wv": ParamSpec((d, kv * hd), ("embed", "kv_proj")),
        "wo": ParamSpec((h * hd, d), ("q_proj", "embed")),
    }
    if cfg.attn.qkv_bias and not cross:
        sp["bq"] = ParamSpec((h * hd,), ("q_proj",), "zeros")
        sp["bk"] = ParamSpec((kv * hd,), ("kv_proj",), "zeros")
        sp["bv"] = ParamSpec((kv * hd,), ("kv_proj",), "zeros")
    if cfg.attn.qk_norm:
        sp["q_norm"] = ParamSpec((hd,), (None,), "zeros")
        sp["k_norm"] = ParamSpec((hd,), (None,), "zeros")
    return sp


def _project_qkv(p, x, cfg: ArchConfig, positions, rope: bool = True):
    B, S, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = linear(x, p["wq"].astype(x.dtype), "wq")
    k = linear(x, p["wk"].astype(x.dtype), "wk")
    v = linear(x, p["wv"].astype(x.dtype), "wv")
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = q.reshape(B, S, h, hd)
    k = k.reshape(B, S, kv, hd)
    v = v.reshape(B, S, kv, hd)
    if cfg.attn.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if rope:
        q = apply_rope(q, positions, cfg.attn.rope_theta, cfg.attn.mrope_sections)
        k = apply_rope(k, positions, cfg.attn.rope_theta, cfg.attn.mrope_sections)
    return q, k, v


def _merge_heads(p, o, cfg: ArchConfig):
    B, S = o.shape[:2]
    o = o.reshape(B, S, cfg.n_heads * cfg.d_head)
    return linear(o, p["wo"].astype(o.dtype), "wo")


def _mask_full(S: int, Skv: int, causal: bool, window: Optional[int], offset: int = 0):
    """(S, Skv) additive mask. offset = index of query 0 within kv timeline."""
    qi = jnp.arange(S)[:, None] + offset
    ki = jnp.arange(Skv)[None, :]
    ok = jnp.ones((S, Skv), dtype=bool)
    if causal:
        ok = ok & (ki <= qi)
    if window is not None:
        ok = ok & (ki > qi - window)
    return jnp.where(ok, 0.0, -1e30).astype(jnp.float32)


def full_attention(q, k, v, cfg: ArchConfig, causal: bool, window, offset: int = 0):
    """Materialized-scores path (seq <= CHUNK_THRESHOLD).  Scores scale by
    the q/k head width; v may be narrower (latent attention)."""
    B, S, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(B, S, kvh, g, hd)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(hd).astype(jnp.float32)
    scores = softcap(scores, cfg.attn.logit_softcap)
    scores = scores + _mask_full(S, k.shape[1], causal, window, offset)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgst,btkd->bskgd", w, v)
    return o.reshape(B, S, h, v.shape[-1])


def chunked_attention(q, k, v, cfg: ArchConfig, causal: bool, window, offset: int = 0):
    """Flash-style online-softmax over KV chunks (no S x Skv materialization)."""
    B, S, h, hd = q.shape
    kvh = k.shape[2]
    Skv = k.shape[1]
    g = h // kvh
    qg = q.reshape(B, S, kvh, g, hd)
    n_chunks = (Skv + KV_CHUNK - 1) // KV_CHUNK
    pad = n_chunks * KV_CHUNK - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(B, n_chunks, KV_CHUNK, kvh, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, KV_CHUNK, kvh, hd).transpose(1, 0, 2, 3, 4)

    qi = jnp.arange(S)[:, None] + offset

    def body(carry, xs):
        m, l, acc = carry
        ci, kci, vci = xs
        ki = ci * KV_CHUNK + jnp.arange(KV_CHUNK)[None, :]
        s = jnp.einsum("bskgd,btkd->bkgst", qg, kci).astype(jnp.float32)
        s = s / jnp.sqrt(hd).astype(jnp.float32)
        s = softcap(s, cfg.attn.logit_softcap)
        ok = ki < Skv
        if causal:
            ok = ok & (ki <= qi)
        if window is not None:
            ok = ok & (ki > qi - window)
        s = s + jnp.where(ok, 0.0, -1e30)[None, None, None, :, :]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        pexp = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + jnp.sum(pexp, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bkgst,btkd->bkgsd", pexp.astype(q.dtype), vci
        ).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, kvh, g, S), -1e30, dtype=jnp.float32)
    l0 = jnp.zeros((B, kvh, g, S), dtype=jnp.float32)
    a0 = jnp.zeros((B, kvh, g, S, v.shape[-1]), dtype=jnp.float32)
    # checkpoint: FlashAttention semantics — recompute chunk scores in the
    # backward instead of saving [*, S, KV_CHUNK] residuals per chunk.
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(body), (m0, l0, a0), (jnp.arange(n_chunks), kc, vc)
    )
    o = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
    return o.transpose(0, 3, 1, 2, 4).reshape(B, S, h, v.shape[-1])


def self_attention(p, x, cfg: ArchConfig, positions, mixer: str):
    """Training/prefill self-attention."""
    window = cfg.attn.sliding_window if mixer == "attn_local" else None
    q, k, v = _project_qkv(p, x, cfg, positions)
    S = x.shape[1]
    fn = chunked_attention if S > CHUNK_THRESHOLD else full_attention
    o = fn(q, k, v, cfg, causal=True, window=window)
    return _merge_heads(p, o, cfg)


def encoder_attention(p, x, cfg: ArchConfig, positions):
    q, k, v = _project_qkv(p, x, cfg, positions)
    o = full_attention(q, k, v, cfg, causal=False, window=None)
    return _merge_heads(p, o, cfg)


def cross_attention(p, x, mem_k, mem_v, cfg: ArchConfig):
    """Decoder cross-attention over precomputed encoder K/V."""
    B, S, _ = x.shape
    h, hd = cfg.n_heads, cfg.d_head
    q = linear(x, p["wq"].astype(x.dtype), "wq").reshape(B, S, h, hd)
    o = full_attention(q, mem_k, mem_v, cfg, causal=False, window=None)
    return _merge_heads(p, o, cfg)


def project_memory_kv(p, mem, cfg: ArchConfig):
    B, S, _ = mem.shape
    kv, hd = cfg.n_kv_heads, cfg.d_head
    k = linear(mem, p["wk"].astype(mem.dtype), "wk").reshape(B, S, kv, hd)
    v = linear(mem, p["wv"].astype(mem.dtype), "wv").reshape(B, S, kv, hd)
    return k, v


# --------------------------------------------------------------------------
# decode (single token, KV cache)
# --------------------------------------------------------------------------
def init_kv_cache(cfg: ArchConfig, batch: int, max_seq: int, dtype) -> Dict[str, jnp.ndarray]:
    kv, hd = cfg.n_kv_heads, cfg.d_head
    return {
        "k": jnp.zeros((batch, max_seq, kv, hd), dtype),
        "v": jnp.zeros((batch, max_seq, kv, hd), dtype),
    }


def decode_self_attention(
    p,
    x: jnp.ndarray,               # (B, 1, d)
    cache: Dict[str, jnp.ndarray],
    pos: jnp.ndarray,             # scalar int32: current position
    cfg: ArchConfig,
    mixer: str,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    B = x.shape[0]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    positions = jnp.broadcast_to(pos, (B, 1))
    q, k, v = _project_qkv(p, x, cfg, positions)
    ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, pos, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, pos, 0, 0))
    ck = constrain(ck, "cache_kv")      # keep long-context caches seq-sharded
    cv = constrain(cv, "cache_kv")
    Skv = ck.shape[1]
    g = h // kvh
    qg = q.reshape(B, 1, kvh, g, hd)
    s = jnp.einsum("bskgd,btkd->bkgst", qg, ck).astype(jnp.float32)
    # keep partial scores sharded like the (possibly seq-sharded) KV cache —
    # otherwise GSPMD all-gathers the full 500k cache per decoded token
    # (measured 2.3 GB/token on jamba long_500k; EXPERIMENTS.md §Perf).
    s = constrain(s, "decode_scores")
    s = s / jnp.sqrt(hd).astype(jnp.float32)
    s = softcap(s, cfg.attn.logit_softcap)
    ki = jnp.arange(Skv)[None, :]
    ok = ki <= pos
    window = cfg.attn.sliding_window if mixer == "attn_local" else None
    if window is not None:
        ok = ok & (ki > pos - window)
    s = s + jnp.where(ok, 0.0, -1e30)[None, None, None]
    w = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    o = jnp.einsum("bkgst,btkd->bskgd", w, cv).reshape(B, 1, h, hd)
    return _merge_heads(p, o, cfg), {"k": ck, "v": cv}

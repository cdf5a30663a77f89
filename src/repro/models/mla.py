"""Multi-head latent attention (MLA), DeepSeek-V3 (arXiv:2412.19437) without
the query low-rank path — the attention of moonlight-16b-a3b.

Per position ``h`` (d_model):

  q = h Wq                       -> per head q_nope (nope) | q_pe (rope)
  h Wkv_a                        -> c_kv (kv_lora_rank) | k_pe (rope, one
                                    for all heads)
  c_kv = RMSNorm(c_kv);  c_kv Wkv_b -> per head k_nope (nope) | v (v_dim)
  RoPE on q_pe and k_pe only; k = k_nope | k_pe;  scores / sqrt(nope+rope)
  o Wo

Every projection goes through ``linear`` with its own tag (``wq``,
``wkv_a``, ``wkv_b``, ``wo``), so the analog forward maps each onto its own
crossbar.  RoPE is rotate-half on contiguous halves of the rope columns;
the published model interleaves pairs, which with random weights is the
same up to a fixed permutation of Wq's and Wkv_a's rope columns.  Decode
keeps the expanded per-head k and v in its cache (no latent cache).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.attention import (CHUNK_THRESHOLD, chunked_attention,
                                    full_attention)
from repro.models.common import ParamSpec, apply_rope, linear, rms_norm


def mla_specs(cfg: ArchConfig) -> Dict[str, ParamSpec]:
    d, h, m = cfg.d_model, cfg.n_heads, cfg.mla
    return {
        "wq": ParamSpec((d, h * m.qk_head_dim), ("embed", "q_proj")),
        "wkv_a": ParamSpec((d, m.kv_lora_rank + m.qk_rope_head_dim),
                           ("embed", None)),
        "kv_norm": ParamSpec((m.kv_lora_rank,), (None,), "zeros"),
        "wkv_b": ParamSpec((m.kv_lora_rank,
                            h * (m.qk_nope_head_dim + m.v_head_dim)),
                           (None, "q_proj")),
        "wo": ParamSpec((h * m.v_head_dim, d), ("q_proj", "embed")),
    }


def project_qkv(p, x, cfg: ArchConfig, positions
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(q, k, v) per head: q and k (B, S, H, nope + rope), v (B, S, H,
    v_dim)."""
    B, S, _ = x.shape
    h, m = cfg.n_heads, cfg.mla
    nope, theta = m.qk_nope_head_dim, cfg.attn.rope_theta
    q = linear(x, p["wq"].astype(x.dtype), "wq").reshape(B, S, h, -1)
    kv = linear(x, p["wkv_a"].astype(x.dtype), "wkv_a")
    c_kv, k_pe = kv[..., :m.kv_lora_rank], kv[..., m.kv_lora_rank:]
    c_kv = rms_norm(c_kv, p["kv_norm"], cfg.norm_eps)
    kvb = linear(c_kv, p["wkv_b"].astype(x.dtype), "wkv_b").reshape(
        B, S, h, -1)
    q_pe = apply_rope(q[..., nope:], positions, theta)
    k_pe = apply_rope(k_pe[:, :, None, :], positions, theta)
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    k = jnp.concatenate(
        [kvb[..., :nope], jnp.broadcast_to(k_pe, (B, S, h, k_pe.shape[-1]))],
        axis=-1)
    return q, k, kvb[..., nope:]


def merge_heads(p, o, cfg: ArchConfig):
    B, S = o.shape[:2]
    return linear(o.reshape(B, S, -1), p["wo"].astype(o.dtype), "wo")


def mla_attention(p, x, cfg: ArchConfig, positions):
    """Training/prefill causal self-attention."""
    q, k, v = project_qkv(p, x, cfg, positions)
    fn = chunked_attention if x.shape[1] > CHUNK_THRESHOLD else full_attention
    return merge_heads(p, fn(q, k, v, cfg, causal=True, window=None), cfg)


def init_mla_cache(cfg: ArchConfig, batch: int, max_seq: int, dtype
                   ) -> Dict[str, jnp.ndarray]:
    h, m = cfg.n_heads, cfg.mla
    return {"k": jnp.zeros((batch, max_seq, h, m.qk_head_dim), dtype),
            "v": jnp.zeros((batch, max_seq, h, m.v_head_dim), dtype)}


def mla_decode(p, x, cache: Dict[str, jnp.ndarray], pos, cfg: ArchConfig):
    """One token (B, 1, d) against the expanded k/v cache."""
    B = x.shape[0]
    q, k, v = project_qkv(p, x, cfg, jnp.broadcast_to(pos, (B, 1)))
    ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, pos, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, pos, 0, 0))
    s = jnp.einsum("bshd,bthd->bhst", q, ck).astype(jnp.float32)
    s = s / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    s = s + jnp.where(jnp.arange(ck.shape[1]) <= pos, 0.0, -1e30)
    w = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    o = jnp.einsum("bhst,bthd->bshd", w, cv)
    return merge_heads(p, o, cfg), {"k": ck, "v": cv}

"""Model substrate: parameter specs, norms, rotary embeddings, activations.

Parameter handling is spec-first (MaxText-style logical axes):

  * each model defines ``param_specs(cfg) -> pytree[ParamSpec]``
  * ``init_params``     — concrete arrays (smoke tests / real training)
  * ``abstract_params`` — ShapeDtypeStructs (dry-run lowering: no allocation)
  * ``logical_axes``    — pytree of logical-axis tuples; the sharding rules
                          table (launch/sharding.py) maps these to mesh axes.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]       # logical axis names
    init: str = "normal"                  # normal | zeros | ones | embed
    dtype: Optional[str] = None           # override cfg.param_dtype

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _leaf_is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def init_params(specs: Any, cfg: ArchConfig, key: jax.Array) -> Any:
    leaves, treedef = jax.tree_util.tree_flatten(specs, is_leaf=_leaf_is_spec)
    keys = jax.random.split(key, len(leaves))

    def mk(spec: ParamSpec, k):
        dtype = jnp.dtype(spec.dtype or cfg.param_dtype)
        if spec.init == "zeros":
            return jnp.zeros(spec.shape, dtype)
        if spec.init == "ones":
            return jnp.ones(spec.shape, dtype)
        fan_in = spec.shape[0] if len(spec.shape) > 1 else max(spec.shape[-1], 1)
        if spec.init == "embed":
            # N(0, 1/d): inputs are rescaled by sqrt(d) at lookup, and tied
            # unembedding then yields O(1) logits at init (Gemma scheme).
            scale = 1.0 / jnp.sqrt(spec.shape[-1])
        else:
            scale = 1.0 / jnp.sqrt(fan_in)
        return (jax.random.normal(k, spec.shape) * scale).astype(dtype)

    return jax.tree_util.tree_unflatten(treedef, [mk(s, k) for s, k in zip(leaves, keys)])


def abstract_params(specs: Any, cfg: ArchConfig) -> Any:
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype or cfg.param_dtype)),
        specs,
        is_leaf=_leaf_is_spec,
    )


def logical_axes(specs: Any) -> Any:
    return jax.tree_util.tree_map(lambda s: s.axes, specs, is_leaf=_leaf_is_spec)


# --------------------------------------------------------------------------
# linear-layer interception (analog IMC routing — DESIGN.md §12)
# --------------------------------------------------------------------------
# All crossbar-mappable GEMMs in the model stack funnel through ``linear``
# (one weight matrix) or ``grouped_linear`` (a stack of expert matrices over
# rows sorted by expert) so `imc.model_analog` can reroute them through the
# differential-conductance MVM without forking the forward code.  The hooks
# are plain module globals (not context-locals): model_analog's unrolled
# forward is eager and single-threaded, and a global keeps the default path
# free of any overhead beyond one ``is None`` check.
_LINEAR_HOOK = None
_GROUPED_HOOK = None


def linear(x: jnp.ndarray, w: jnp.ndarray, tag: str = "") -> jnp.ndarray:
    """``x @ w`` with optional interception.

    ``x`` may have any number of leading dims; ``w`` is 2-D (K, N).  The hook
    (if installed) receives a 2-D ``(M, K)`` view plus the site tag and must
    return ``(M, N)``.
    """
    if _LINEAR_HOOK is None:
        return x @ w
    lead = x.shape[:-1]
    y = _LINEAR_HOOK(x.reshape(-1, x.shape[-1]), w, tag)
    return y.reshape(*lead, w.shape[-1])


def group_ids(group_sizes: jnp.ndarray, rows: int) -> jnp.ndarray:
    """(rows,) group of each row of a block sorted by group; rows past the
    groups' total get ``len(group_sizes)``."""
    ends = jnp.cumsum(group_sizes)
    return jnp.searchsorted(ends, jnp.arange(rows), side="right")


def grouped_linear(x: jnp.ndarray, w: jnp.ndarray, group_sizes: jnp.ndarray,
                   tag: str = "") -> jnp.ndarray:
    """Row block ``g`` of ``x`` times ``w[g]``: the rows ``(M, K)`` are sorted
    by group, group ``g`` holds the next ``group_sizes[g]`` rows, and rows
    past the total come back zero.  ``w`` is ``(G, K, N)``.

    With a grouped hook installed it receives ``(x, w, group_sizes, tag)``;
    with only a ``linear`` hook, each group's rows go through it as their
    own product (the other rows zeroed), so no group is computed outside
    the hook."""
    if _GROUPED_HOOK is not None:
        return _GROUPED_HOOK(x, w, group_sizes, tag)
    if _LINEAR_HOOK is None:
        return jax.lax.ragged_dot(x, w, group_sizes)
    gid = group_ids(group_sizes, x.shape[0])[:, None]
    y = jnp.zeros((x.shape[0], w.shape[-1]), jnp.float32)
    for g in range(w.shape[0]):
        y = y + jnp.where(gid == g, _LINEAR_HOOK(jnp.where(gid == g, x, 0),
                                                 w[g], tag), 0.0)
    return y.astype(x.dtype)


class intercept_linears:
    """Context manager installing ``hook(x2d, w, tag) -> y2d`` on ``linear``
    and, optionally, ``grouped(x, w_stack, group_sizes, tag) -> y`` on
    ``grouped_linear``."""

    def __init__(self, hook, grouped=None):
        self.hook = hook
        self.grouped = grouped

    def __enter__(self):
        global _LINEAR_HOOK, _GROUPED_HOOK
        self._prev = (_LINEAR_HOOK, _GROUPED_HOOK)
        _LINEAR_HOOK, _GROUPED_HOOK = self.hook, self.grouped
        return self

    def __exit__(self, *exc):
        global _LINEAR_HOOK, _GROUPED_HOOK
        _LINEAR_HOOK, _GROUPED_HOOK = self._prev
        return False


# --------------------------------------------------------------------------
# numerics
# --------------------------------------------------------------------------
def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dt)


def softcap(x: jnp.ndarray, cap: Optional[float]) -> jnp.ndarray:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


def act_fn(x: jnp.ndarray, kind: str) -> jnp.ndarray:
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x, approximate=True)
    raise ValueError(kind)


# --------------------------------------------------------------------------
# rotary embeddings (standard + M-RoPE)
# --------------------------------------------------------------------------
def rope_freqs(d_head: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, d_head // 2, dtype=jnp.float32) / (d_head // 2)))


def apply_rope(
    x: jnp.ndarray,               # (B, S, H, D)
    positions: jnp.ndarray,       # (B, S) or (3, B, S) for M-RoPE
    theta: float,
    mrope_sections: Optional[Tuple[int, int, int]] = None,
) -> jnp.ndarray:
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (D/2,)
    if mrope_sections is None:
        if positions.ndim == 3:
            positions = positions[0]
        angles = positions[..., None].astype(jnp.float32) * freqs  # (B,S,D/2)
    else:
        # M-RoPE: frequency dims split into (temporal, height, width)
        # sections, each rotated by its own position stream.
        if positions.ndim == 2:
            positions = jnp.broadcast_to(positions[None], (3,) + positions.shape)
        parts = []
        start = 0
        for sec, pos in zip(mrope_sections, positions):
            parts.append(pos[..., None].astype(jnp.float32) * freqs[start:start + sec])
            start += sec
        angles = jnp.concatenate(parts, axis=-1)       # (B,S,D/2)
    cos = jnp.cos(angles)[..., None, :]                # (B,S,1,D/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)

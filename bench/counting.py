"""Work counted from shapes: the numerators of the roofline and MFU metrics.

Nothing here runs on a device or reads the program; every count follows
from a configuration's sizes or a kernel call's shapes.
"""
from __future__ import annotations

from typing import Iterable, Tuple

import jax
import numpy as np

F32 = 4


def fake_analog_work(shapes: Iterable[Tuple[int, int, int]]):
    """(operations, bytes) of fake-analog kernel calls of shapes (M, K, N).

    Operations: the 2*M*K*N of the multiply-accumulate.  Bytes: the float32
    operands read once and the result written once: read voltages (M, K),
    normalised weights and fail codes (K, N) each, the (8, N) aux plane and
    the (M, N) output."""
    ops = 0.0
    nbytes = 0.0
    for m, k, n in shapes:
        ops += 2.0 * m * k * n
        nbytes += F32 * (m * k + 2 * k * n + 8 * n + m * n)
    return ops, nbytes


def roofline_seconds(ops: float, nbytes: float, peaks: dict):
    """(least time on the chip, which bound binds: "compute" or "memory")."""
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


def decoder_linear_shapes(model: dict, tokens: int):
    """(M, K, N) of every linear of one full-sequence forward of a dense
    decoder with grouped-query attention and a gated FFN, in order, with the
    output head last."""
    d, hd = model["d_model"], model["d_head"]
    q, kv, ff = model["n_heads"] * hd, model["n_kv_heads"] * hd, model["d_ff"]
    layer = [(tokens, d, q), (tokens, d, kv), (tokens, d, kv), (tokens, q, d),
             (tokens, d, ff), (tokens, d, ff), (tokens, ff, d)]
    return layer * model["n_layers"] + [(tokens, d, model["vocab"])]


def decoder_forward_flops(model: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one full-sequence forward: every linear including the
    output head, plus attention's score and value products over the whole
    (seq x seq) square each head computes (the causal mask is applied after
    the product)."""
    lin = sum(2.0 * m * k * n for m, k, n in
              decoder_linear_shapes(model, batch * seq))
    attn = (4.0 * batch * model["n_heads"] * seq * seq * model["d_head"]
            * model["n_layers"])
    return lin + attn


# ------------------------------------------------ jaxpr dot-FLOP walk
def _dot_flops(eqn) -> float:
    lhs, rhs = (v.aval for v in eqn.invars[:2])
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    batch = int(np.prod([lhs.shape[i] for i in lb])) if lb else 1
    contract = int(np.prod([lhs.shape[i] for i in lc])) if lc else 1
    m = int(np.prod([lhs.shape[i] for i in range(lhs.ndim)
                     if i not in lc and i not in lb]))
    n = int(np.prod([rhs.shape[i] for i in range(rhs.ndim)
                     if i not in rc and i not in rb]))
    return 2.0 * batch * m * n * contract


_SUBJAXPR_PARAMS = ("jaxpr", "call_jaxpr", "body_jaxpr", "fun_jaxpr",
                    "branches")


def count_jaxpr_flops(jaxpr) -> float:
    """Matmul FLOPs of a jaxpr, scans multiplied by their trip counts."""
    total = 0.0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            total += _dot_flops(eqn)
        elif name == "scan":
            total += (count_jaxpr_flops(eqn.params["jaxpr"].jaxpr)
                      * eqn.params["length"])
        else:
            for pname in _SUBJAXPR_PARAMS:
                if pname in eqn.params:
                    sub = eqn.params[pname]
                    for s in (sub if isinstance(sub, (list, tuple)) else [sub]):
                        j = getattr(s, "jaxpr", s)
                        if hasattr(j, "eqns"):
                            total += count_jaxpr_flops(j)
    return total


def audit_flops(fn, *args) -> float:
    return count_jaxpr_flops(jax.make_jaxpr(fn)(*args).jaxpr)

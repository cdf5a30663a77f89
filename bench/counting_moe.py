"""Work of the expert-layer analog forward, counted from shapes and from the
rows each held expert was routed: the numerators of the MoE cell's roofline
and MFU metrics.

Nothing here runs on a device or reads the program; the routed rows come
from the group sizes the forwards returned with their logits.
"""
from __future__ import annotations

from typing import Iterable, Tuple

from bench import counting

F32 = counting.F32


def grouped_work(rows: int, experts: int, k: int, n: int):
    """(operations, bytes) of one grouped fake-analog call over ``rows``
    routed rows and ``experts`` crossbars of (k, n): the multiply-accumulate
    of the routed rows, and the float32 operands read once (the rows, each
    expert's normalised weights and fail codes, its (8, n) aux plane) and
    the routed rows' output written once."""
    ops = 2.0 * rows * k * n
    nbytes = F32 * (rows * k + experts * (2 * k * n + 8 * n) + rows * n)
    return ops, nbytes


def expert_layer_work(rows: int, m: dict):
    """(operations, bytes) of one expert layer's three grouped calls (gate,
    up: d -> f; down: f -> d) over ``rows`` routed rows."""
    d, f, e = m["d_model"], m["d_expert"], m["n_held"]
    parts = [grouped_work(rows, e, d, f), grouped_work(rows, e, d, f),
             grouped_work(rows, e, f, d)]
    return sum(p[0] for p in parts), sum(p[1] for p in parts)


def grouped_window_work(sizes: Iterable[Iterable[Iterable[int]]], m: dict):
    """(operations, bytes) of the grouped calls of the window's forwards,
    from each forward's rows per held expert per layer."""
    ops = nbytes = 0.0
    for forward in sizes:
        for layer in forward:
            o, b = expert_layer_work(int(sum(layer)), m)
            ops, nbytes = ops + o, nbytes + b
    return ops, nbytes


def dense_linear_shapes(m: dict, tokens: int) -> Tuple[Tuple[int, int, int], ...]:
    """(M, K, N) of every linear of one forward that is not a routed
    expert: each layer's latent-attention projections, the leading dense
    layers' FFN, the expert layers' router and shared experts, and the
    output head last."""
    d, h = m["d_model"], m["n_heads"]
    qk = m["qk_nope"] + m["qk_rope"]
    mla = [(tokens, d, h * qk), (tokens, d, m["kv_lora"] + m["qk_rope"]),
           (tokens, m["kv_lora"], h * (m["qk_nope"] + m["v_dim"])),
           (tokens, h * m["v_dim"], d)]
    ffn = [(tokens, d, m["d_ff"])] * 2 + [(tokens, m["d_ff"], d)]
    shared = [(tokens, d, m["d_shared"])] * 2 + [(tokens, m["d_shared"], d)]
    moe = [(tokens, d, m["n_experts"])] + shared
    lead = m["first_k_dense"]
    return tuple((mla + ffn) * lead
                 + (mla + moe) * (m["n_layers"] - lead)
                 + [(tokens, d, m["vocab"])])


def forward_flops(m: dict, batch: int, seq: int, routed_rows: int) -> float:
    """Model FLOPs of one forward as executed on this chip: every linear
    including the router and the head, the held experts' products over the
    rows routed to them (summed over layers), and attention's score and
    value products over the whole (seq x seq) square of every head."""
    lin = sum(2.0 * mm * k * n
              for mm, k, n in dense_linear_shapes(m, batch * seq))
    d, f = m["d_model"], m["d_expert"]
    experts = 2.0 * routed_rows * (2 * d * f + f * d)
    attn = (2.0 * batch * m["n_heads"] * seq * seq
            * (m["qk_nope"] + m["qk_rope"] + m["v_dim"]) * m["n_layers"])
    return lin + experts + attn

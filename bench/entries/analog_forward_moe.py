"""Entry: ``imc.model_analog.analog_model_logits(mode="fake")`` on a
DeepSeek-V3-style expert model (latent attention, a leading dense layer,
sigmoid-routed experts with shared experts) — one analog-routed forward per
call, on one chip's expert-parallel share.

The benchmark makes the model's weights from the seed, on the device, in one
jitted call, in the program's parameter layout and the configuration's
float32; the plain reference then sets the router's selection bias so that
the experts' loads come out even over batches drawn from the seed
(``balance_router``).  One call runs the whole forward of a (batch x seq) block of token
ids drawn from the call's seed over the configuration's vocabulary slice,
with every linear (each expert's and the router's included) through the
fused fake-analog kernels, under float32 matmuls at ``highest``; it ends
when the logits are ready.  The forward also returns each expert layer's
chosen experts and the rows routed to each held expert: the rows count the
window's grouped-kernel work, and one forward of the window, drawn from the
run's seed by reservoir sampling, keeps its logits and expert ids for the
check.

The check runs the plain reference (``bench/reference/moonlight_analog.py``)
over the same weights and tokens, layer by layer, each held expert over the
tokens routed to it, and the output head in row blocks, and compares:

  mean_abs_gap    mean |program - reference| over the mean |reference| of
                  the logits at every position;
  max_abs_gap     max |program - reference| over the max |reference|;
  route_mismatch  the expert choices of the program that the reference did
                  not make, outside routing ties (limit: none).

A routing tie is a token whose choice the two make differently while every
expert in the difference lies within ``TIE_MARGIN`` of the reference's own
boundary between its 6th and 7th biased router score; the reference then
follows the program's choice, so that one legitimate flip does not move
every later layer.  ``TIE_MARGIN`` is 1e-4: the two compute the router's
scores through the same crossbar reads in float32 and differ by rounding
(about 1e-6 of the residual stream), a hundredth of the margin, while the
gap between the 6th and 7th score is about 1e-2; a fault that moves a
score by 1e-4 or more is counted.
"""
from __future__ import annotations

import functools
import random

import jax
import jax.numpy as jnp
import numpy as np

from bench import counting_moe
from bench.entries import analog_forward as dense
from bench.reference import moonlight_analog as ref
from bench.reference.qwen2_analog import Frozen

TIE_MARGIN = 1e-4


def model_dims(cfg: dict) -> Frozen:
    """The configuration's sizes, after checking that it states the
    architecture the program runs."""
    want = {"scoring_func": "sigmoid", "topk_method": "noaux_tc",
            "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
            "q_lora_rank": None, "moe_layer_freq": 1, "hidden_act": "silu",
            "tie_word_embeddings": False, "attention_bias": False}
    for key, val in want.items():
        if cfg[key] != val:
            raise ValueError(f"{key} {cfg[key]!r}: the program runs {val!r}")
    ep = cfg["expert_parallel"]
    n_layers = int(cfg["num_hidden_layers"])
    return Frozen(
        n_layers=n_layers, first_k_dense=int(cfg["first_k_dense_replace"]),
        d_model=int(cfg["hidden_size"]),
        n_heads=int(cfg["num_attention_heads"]),
        kv_lora=int(cfg["kv_lora_rank"]), qk_nope=int(cfg["qk_nope_head_dim"]),
        qk_rope=int(cfg["qk_rope_head_dim"]), v_dim=int(cfg["v_head_dim"]),
        d_ff=int(cfg["intermediate_size"]),
        d_expert=int(cfg["moe_intermediate_size"]),
        d_shared=int(cfg["moe_intermediate_size"])
        * int(cfg["n_shared_experts"]),
        n_experts=int(ep["router_experts"]),
        held_first=int(ep["held_first_expert"]),
        n_held=int(cfg["n_routed_experts"]),
        top_k=int(cfg["num_experts_per_tok"]),
        routed_scale=float(cfg["routed_scaling_factor"]),
        vocab=int(cfg["vocab_size"]), rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        compute_dtype=cfg["precision"]["compute_dtype"])


def _program_config(cfg: dict, m: dict):
    from repro.configs.base import ArchConfig, AttnConfig, MLAConfig, MoEConfig

    return ArchConfig(
        name=cfg["name"], family="moe", n_layers=m["n_layers"],
        d_model=m["d_model"], n_heads=m["n_heads"],
        n_kv_heads=m["n_heads"], d_head=m["qk_nope"] + m["qk_rope"],
        d_ff=m["d_ff"], vocab=m["vocab"],
        attn=AttnConfig(rope_theta=m["rope_theta"]),
        mla=MLAConfig(kv_lora_rank=m["kv_lora"],
                      qk_nope_head_dim=m["qk_nope"],
                      qk_rope_head_dim=m["qk_rope"], v_head_dim=m["v_dim"]),
        moe=MoEConfig(num_experts=m["n_experts"], top_k=m["top_k"],
                      d_expert=m["d_expert"], shared_expert=True,
                      d_shared=m["d_shared"], score="sigmoid",
                      routed_scale=m["routed_scale"],
                      held=(m["held_first"], m["n_held"])),
        pattern=(("mla", "moe"),), first_k_dense=m["first_k_dense"],
        scale_embed=False, act=cfg["hidden_act"], norm_eps=m["norm_eps"],
        param_dtype=cfg["precision"]["params"],
        compute_dtype=m["compute_dtype"])


@functools.partial(jax.jit, static_argnames=("spec",))
def _normal_leaves(key, spec):
    keys = jax.random.split(key, len(spec))
    return [jax.random.normal(k, shape, jnp.float32) * scale
            for k, (shape, scale) in zip(keys, spec)]


def make_params(m: dict, seed: int):
    """Random float32 weights in the program's layout, on the device, in one
    jitted call: matrices N(0, 1/fan_in), the embedding N(0, 1/d), norm
    scales (applied as 1 + scale) N(0, 0.1^2), the router's selection bias
    N(0, 1e-3^2) (``balance_router`` then sets it)."""
    Leaf = dense._Leaf
    d, h, r = m["d_model"], m["n_heads"], m["kv_lora"]
    qk, rp = m["qk_nope"] + m["qk_rope"], m["qk_rope"]
    f, fs, e = m["d_expert"], m["d_shared"], m["n_held"]

    def mat(lead, k, n):
        return Leaf(lead + (k, n), 1.0 / np.sqrt(k))

    def layer(n, ffn):
        return {"ln1": Leaf((n, d), 0.1), "ln2": Leaf((n, d), 0.1),
                "attn": {"wq": mat((n,), d, h * qk),
                         "wkv_a": mat((n,), d, r + rp),
                         "kv_norm": Leaf((n, r), 0.1),
                         "wkv_b": mat((n,), r, h * (m["qk_nope"] + m["v_dim"])),
                         "wo": mat((n,), h * m["v_dim"], d)},
                "ffn": ffn}

    lead, rest = m["first_k_dense"], m["n_layers"] - m["first_k_dense"]
    shapes = {
        "embed": Leaf((m["vocab"], d), 1.0 / np.sqrt(d)),
        "unembed": mat((), d, m["vocab"]),
        "final_norm": Leaf((d,), 0.1),
        "lead": {"pos0": layer(lead, {
            "w_gate": mat((lead,), d, m["d_ff"]),
            "w_up": mat((lead,), d, m["d_ff"]),
            "w_down": mat((lead,), m["d_ff"], d)})},
        "blocks": {"pos0": layer(rest, {
            "router": mat((rest,), d, m["n_experts"]),
            "router_bias": Leaf((rest, m["n_experts"]), 1e-3),
            "w_gate": mat((rest, e), d, f), "w_up": mat((rest, e), d, f),
            "w_down": mat((rest, e), f, d),
            "shared": {"w_gate": mat((rest,), d, fs),
                       "w_up": mat((rest,), d, fs),
                       "w_down": mat((rest,), fs, d)}})},
    }
    leaves, tree = jax.tree_util.tree_flatten(
        shapes, is_leaf=lambda x: isinstance(x, Leaf))
    vals = _normal_leaves(jax.random.PRNGKey(seed),
                          tuple((x.shape, x.scale) for x in leaves))
    return jax.tree_util.tree_unflatten(tree, vals)


BALANCE_BATCHES = 4    # batches of the traffic's shape that set the bias


def balance_router(state, seed):
    """Set each expert layer's selection bias so that the routed experts'
    loads come out even, as training leaves it (DeepSeek-V3 moves each
    expert's bias against its load): the plain reference routes
    ``BALANCE_BATCHES`` batches of the traffic's shape, drawn from
    ``seed``, and sets the bias layer by layer on its own router scores
    (``ref.balanced_bias``); the program is not run.  Random router
    weights alone send the held experts 0.85 to 1.1 of their even share
    of the rows, by seed, and the forward's time with it."""
    t, m = state["t"], state["m"]
    key = jax.random.PRNGKey(seed)
    batches = [jax.random.randint(jax.random.fold_in(key, i),
                                  (int(t["batch"]), int(t["seq"])), 0,
                                  m["vocab"])
               for i in range(BALANCE_BATCHES)]
    ffn = state["params"]["blocks"]["pos0"]["ffn"]
    ffn["router_bias"] = jnp.asarray(ref.balanced_bias(
        state["params"], batches, m, state["a"], "highest"))


def setup(cfg, wl, seed, devices):
    dense._check_analog_constants(cfg)
    m = model_dims(cfg)
    state = {"cfg": cfg, "t": wl["traffic"], "m": m,
             "a": dense.analog_consts(cfg), "arch": _program_config(cfg, m),
             "acfg": dense._analog_config(cfg), "params": make_params(m, seed),
             "rng": random.Random(seed), "kept": None, "sizes": []}
    balance_router(state, seed)
    call(state, -1, 0)                   # the warm call: compiles or loads
    return state


def reseed(state, seed):
    """New weights and a new reservoir for ``seed`` (calibration); the old
    weights go first, since two sets do not fit beside a forward."""
    state["params"] = state["kept"] = None
    state["params"] = make_params(state["m"], seed)
    balance_router(state, seed)
    state["rng"] = random.Random(seed)
    state["kept"] = None
    state["sizes"] = []


def _forward(state, tokens):
    from repro.imc.model_analog import analog_model_logits

    with jax.default_matmul_precision("highest"):
        return analog_model_logits(state["params"], state["arch"], tokens,
                                   state["acfg"], mode="fake",
                                   with_routes=True)


def call(state, index, seed):
    tokens = dense._tokens(state["t"], state["m"]["vocab"], seed)
    logits, routes = jax.block_until_ready(_forward(state, tokens))
    if index >= 0:
        state["sizes"].append(routes["sizes"])
        # reservoir of one: after n calls each is the kept one with chance 1/n
        if state["rng"].randrange(index + 1) == 0:
            state["kept"] = (tokens, logits, routes["ids"])
    return {"seed": seed}


def work(state):
    """The window's work: grouped-kernel operations and bytes from the rows
    each held expert was routed, the other kernels' shapes per forward, and
    model FLOPs as executed, per forward on average."""
    t, m = state["t"], state["m"]
    b, s = int(t["batch"]), int(t["seq"])
    sizes = [np.asarray(x) for x in state["sizes"]]
    flops = sum(counting_moe.forward_flops(m, b, s, int(x.sum()))
                for x in sizes)
    return {"fake_analog_moe_window": counting_moe.grouped_window_work(
                sizes, m),
            "fake_analog_dense_shapes": counting_moe.dense_linear_shapes(
                m, b * s),
            "model_flops": flops / max(len(sizes), 1)}


COMPARED = ("mean_abs_gap", "max_abs_gap", "route_mismatch")


def _reference(state, tokens, mode, route_ids):
    x, routing = ref.final_hidden(state["params"], tokens, state["m"],
                                  state["a"], mode, route_ids, TIE_MARGIN)
    return ref.head(state["params"], x, state["m"], state["a"], mode), routing


def calibration(state, records, seed):
    """Every reading of the check's comparison (for setting its limits):
    the logit readings of ``analog_forward.readings``, the routing's
    mismatches and ties."""
    tokens, logits, ids = state["kept"]
    flat = logits.reshape(-1, logits.shape[-1])
    want, routing = _reference(state, tokens, "highest", np.asarray(ids))
    got = dense.readings(want, lambda lo, hi: flat[lo:hi], flat.shape[0])
    got["route_mismatch"] = routing["route_mismatch"]
    got["route_ties"] = routing["route_ties"]
    return got


def check(state, records, seed):
    got = calibration(state, records, seed)
    return {k: got[k] for k in COMPARED}


def control(state, records, seed):
    """The check's numbers with the reference at ``high`` (three bfloat16
    products for every float32 one) put in the program's place, routed as
    the program routed."""
    tokens, _, ids = state["kept"]
    ids = np.asarray(ids)
    want, _ = _reference(state, tokens, "highest", ids)
    got_rows, got_routing = _reference(state, tokens, "high", ids)
    out = dense.readings(want, got_rows, int(tokens.size))
    out["route_mismatch"] = got_routing["route_mismatch"]
    out["route_ties"] = got_routing["route_ties"]
    return out

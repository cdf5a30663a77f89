"""Entry: ``imc.model_analog.analog_model_logits(mode="fake")`` — one
analog-routed forward per call.

The benchmark makes the model's weights from the seed, on the device, in
one jitted call, in the program's parameter layout and the configuration's
float32.  One call runs the whole forward of a (batch x seq) block of token
ids, drawn from the call's seed over the whole vocabulary, with every
linear through the fused fake-analog kernel at the configuration's ADC
width, under float32 matmuls at ``highest``; it ends when the logits are
ready.  One forward of the window, drawn from the run's seed by reservoir
sampling, keeps its logits for the check.

The check runs the plain reference (``bench/reference/qwen2_analog.py``)
over the same weights and tokens, layer by layer, and the output head in
row blocks, and compares the logits at every position:

  mean_abs_gap  mean |program - reference| over the mean |reference|;
  max_abs_gap   max |program - reference| over the max |reference|.

Calibration also reads the mean KL(reference || program), the share of
positions whose top token moved, and the shares of positions and logits a
head ADC level or more off, which were the candidates for the ADC-8
configuration (PERF.md).
"""
from __future__ import annotations

import functools
import random

import jax
import jax.numpy as jnp
import numpy as np

from bench import counting, program
from bench.reference import qwen2_analog as ref

HEAD_ROWS = 256


def model_dims(cfg: dict) -> ref.Frozen:
    m = cfg["model"]
    return ref.Frozen(
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], n_kv_heads=m["num_key_value_heads"],
        d_head=m["hidden_size"] // m["num_attention_heads"],
        d_ff=m["intermediate_size"], vocab=m["vocab_size"],
        rope_theta=float(m["rope_theta"]), norm_eps=float(m["rms_norm_eps"]),
        compute_dtype=cfg["precision"]["compute_dtype"])


def analog_consts(cfg: dict) -> ref.Frozen:
    an = cfg["analog"]
    g_ap, g_fs = ref.cell_constants(cfg["device"], an["r_access_ohm"])
    return ref.Frozen(g_ap=float(g_ap), g_fs=float(g_fs),
                      r_wire=float(an["r_wire_per_cell_ohm"]),
                      v_read=float(an["v_read"]),
                      fs_sigmas=float(an["full_scale_sigmas"]),
                      adc_bits=int(an["adc_bits"]))


def _program_config(cfg: dict):
    from repro.configs.base import ArchConfig, AttnConfig

    m = model_dims(cfg)
    return ArchConfig(
        name=cfg["name"], family="dense", n_layers=m["n_layers"],
        d_model=m["d_model"], n_heads=m["n_heads"],
        n_kv_heads=m["n_kv_heads"], d_head=m["d_head"], d_ff=m["d_ff"],
        vocab=m["vocab"],
        attn=AttnConfig(qkv_bias=True, rope_theta=m["rope_theta"]),
        pattern=(("attn", "dense"),), tie_embeddings=True,
        act=cfg["model"]["hidden_act"], norm_eps=m["norm_eps"],
        param_dtype=cfg["precision"]["params"],
        compute_dtype=m["compute_dtype"])


def _analog_config(cfg: dict):
    from repro.imc import AnalogConfig

    an = cfg["analog"]
    return AnalogConfig(adc_bits=int(an["adc_bits"]), v_read=an["v_read"],
                        ir_drop=bool(an["ir_drop"]),
                        full_scale_sigmas=an["full_scale_sigmas"])


def _check_analog_constants(cfg: dict):
    """The configuration's read-path numbers must be the program's."""
    from repro.circuit.bitline import BitlineParams

    an = cfg["analog"]
    bl = BitlineParams()
    program.afmtj_params(cfg)
    if (bl.r_access != an["r_access_ohm"]
            or bl.r_wire_per_cell != an["r_wire_per_cell_ohm"]):
        raise ValueError("configuration read path differs from the program's")


class _Leaf:
    def __init__(self, shape, scale):
        self.shape, self.scale = tuple(shape), float(scale)


@functools.partial(jax.jit, static_argnames=("spec",))
def _normal_leaves(key, spec):
    keys = jax.random.split(key, len(spec))
    return [jax.random.normal(k, shape, jnp.float32) * scale
            for k, (shape, scale) in zip(keys, spec)]


def make_params(m: dict, seed: int):
    """Random float32 weights in the program's layout, on the device, in one
    jitted call: matrices N(0, 1/fan_in), the embedding N(0, 1/d), biases
    N(0, 0.02^2) and norm scales (applied as 1 + scale) N(0, 0.1^2)."""
    L, d, ff, v = m["n_layers"], m["d_model"], m["d_ff"], m["vocab"]
    q, kv = m["n_heads"] * m["d_head"], m["n_kv_heads"] * m["d_head"]
    mat = (lambda k, n: _Leaf((L, k, n), 1.0 / np.sqrt(k)))
    shapes = {
        "embed": _Leaf((v, d), 1.0 / np.sqrt(d)),
        "final_norm": _Leaf((d,), 0.1),
        "blocks": {"pos0": {
            "ln1": _Leaf((L, d), 0.1), "ln2": _Leaf((L, d), 0.1),
            "attn": {"wq": mat(d, q), "wk": mat(d, kv), "wv": mat(d, kv),
                     "wo": mat(q, d), "bq": _Leaf((L, q), 0.02),
                     "bk": _Leaf((L, kv), 0.02), "bv": _Leaf((L, kv), 0.02)},
            "ffn": {"w_gate": mat(d, ff), "w_up": mat(d, ff),
                    "w_down": mat(ff, d)}}},
    }
    leaves, tree = jax.tree_util.tree_flatten(
        shapes, is_leaf=lambda x: isinstance(x, _Leaf))
    vals = _normal_leaves(jax.random.PRNGKey(seed),
                          tuple((x.shape, x.scale) for x in leaves))
    return jax.tree_util.tree_unflatten(tree, vals)


def _tokens(t: dict, vocab: int, seed: int):
    return jax.random.randint(jax.random.PRNGKey(seed),
                              (int(t["batch"]), int(t["seq"])), 0, vocab)


def setup(cfg, wl, seed, devices):
    _check_analog_constants(cfg)
    t = wl["traffic"]
    m = model_dims(cfg)
    state = {"cfg": cfg, "t": t, "m": m, "a": analog_consts(cfg),
             "arch": _program_config(cfg), "acfg": _analog_config(cfg),
             "params": make_params(m, seed), "rng": random.Random(seed),
             "kept": None}
    call(state, -1, 0)                   # the warm call: compiles or loads
    return state


def reseed(state, seed):
    """New weights and a new reservoir for ``seed`` (calibration)."""
    state["params"] = make_params(state["m"], seed)
    state["rng"] = random.Random(seed)
    state["kept"] = None


def _forward(state, tokens):
    from repro.imc.model_analog import analog_model_logits

    with jax.default_matmul_precision("highest"):
        return analog_model_logits(state["params"], state["arch"], tokens,
                                   state["acfg"], mode="fake")


def call(state, index, seed):
    tokens = _tokens(state["t"], state["m"]["vocab"], seed)
    logits = jax.block_until_ready(_forward(state, tokens))
    # reservoir of one: after n calls each is the kept one with chance 1/n
    if index >= 0 and state["rng"].randrange(index + 1) == 0:
        state["kept"] = (tokens, logits)
    return {"seed": seed}


def work(state):
    t, m = state["t"], state["m"]
    b, s = int(t["batch"]), int(t["seq"])
    return {"fake_analog_shapes": counting.decoder_linear_shapes(m, b * s),
            "model_flops": counting.decoder_forward_flops(m, b, s)}


COMPARED = ("mean_abs_gap", "max_abs_gap")


def _metrics_rows(ref_rows, got_rows):
    lr = jax.nn.log_softmax(ref_rows, axis=-1)
    lg = jax.nn.log_softmax(got_rows, axis=-1)
    kl = jnp.sum(jnp.exp(lr) * (lr - lg), axis=-1)
    moved = jnp.argmax(ref_rows, -1) != jnp.argmax(got_rows, -1)
    # every reference logit is a whole number of ADC levels of the head
    level = jnp.max(jnp.abs(ref_rows)) / 127.0
    off = jnp.abs(ref_rows - got_rows) > 0.5 * level
    gap = jnp.abs(ref_rows - got_rows)
    return kl, (jnp.sum(moved), jnp.sum(jnp.any(off, -1)), jnp.sum(off),
                jnp.sum(gap), jnp.sum(jnp.abs(ref_rows)), jnp.max(gap),
                jnp.max(jnp.abs(ref_rows)))


_metrics_rows_jit = jax.jit(_metrics_rows)


def readings(ref_rows_fn, got_rows_fn, n_rows: int) -> dict:
    """Every reading of the comparison: the compared ``kl_mean`` and
    ``top1_moved``, and for calibration the share of positions with any
    logit a level or more off, the share of such logits, and the mean
    |difference| over the mean |logit|."""
    tot = np.zeros(5)
    peak = np.zeros(2)
    kls = []
    n_cols = None
    for lo in range(0, n_rows, HEAD_ROWS):
        ref_rows = ref_rows_fn(lo, lo + HEAD_ROWS)
        n_cols = ref_rows.shape[-1]
        kl, sums = _metrics_rows_jit(ref_rows, got_rows_fn(lo, lo + HEAD_ROWS))
        kls.append(np.asarray(kl))
        sums = [float(v) for v in sums]
        tot += np.array(sums[:5])
        peak = np.maximum(peak, sums[5:])
    moved, pos_off, logits_off, abs_diff, abs_ref = tot
    kls = np.concatenate(kls)
    return {"kl_mean": float(kls.mean()), "top1_moved": moved / n_rows,
            "kl_median": float(np.median(kls)),
            "positions_off": pos_off / n_rows,
            "logits_off": logits_off / (n_rows * n_cols),
            "mean_abs_gap": abs_diff / abs_ref,
            "max_abs_gap": peak[0] / peak[1]}


def _reference_rows(state, tokens, mode):
    x = ref.final_hidden(state["params"], tokens, state["m"], state["a"], mode)
    return ref.head(state["params"], x, state["m"], state["a"], mode)


def calibration(state, records, seed):
    """Every reading of the check's comparison (for setting its limits)."""
    tokens, logits = state["kept"]
    flat = logits.reshape(-1, logits.shape[-1])
    want = _reference_rows(state, tokens, "highest")
    return readings(want, lambda lo, hi: flat[lo:hi], flat.shape[0])


def check(state, records, seed):
    got = calibration(state, records, seed)
    return {k: got[k] for k in COMPARED}


def control(state, records, seed):
    """The check's numbers with the reference at ``high`` (three bfloat16
    products for every float32 one) put in the program's place."""
    tokens, _ = state["kept"]
    want = _reference_rows(state, tokens, "highest")
    got = _reference_rows(state, tokens, "high")
    return readings(want, got, int(tokens.size))

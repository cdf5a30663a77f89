"""The expert-layer analog forward cell at a tiny size on the CPU: a sound
run is correct with no routing mismatch, the control at ``high`` reads far
above it, a dispatch that leaves rows out is not correct, and the
expert-parallel shares add up to the whole layer."""
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_tiny import assert_result_shape
from bench import run

CELL = "moonlight-16b-a3b.analog_eval"
TINY = {"hidden_size": 64, "intermediate_size": 128,
        "moe_intermediate_size": 32, "n_shared_experts": 2,
        "n_routed_experts": 4, "num_experts_per_tok": 2,
        "num_hidden_layers": 2, "num_attention_heads": 4, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "vocab_size": 512}


def tiny_cell(held_first=4):
    wl, cfg = run.load_cell(CELL)
    wl["traffic"].update({"batch": 2, "seq": 32})
    cfg.update(TINY)
    cfg["expert_parallel"] = {"chips_sharing_each_expert_layer": 2,
                              "held_first_expert": held_first,
                              "router_experts": 8}
    return wl, cfg


def run_tiny(seed=12345):
    wl, cfg = tiny_cell()
    return run.run_cell(wl, cfg, seed, 0.0, False,
                        devices=jax.devices()[:1], t0=time.perf_counter(),
                        log=lambda *a: None)


@pytest.fixture
def fresh_forward():
    from repro.imc import model_analog

    def clear():
        model_analog._jitted_fake_forward.cache_clear()
        jax.clear_caches()

    clear()
    yield
    clear()


def test_sound_run_is_correct():
    res = run_tiny()
    assert_result_shape(res, "analog_forward_ms")
    assert set(res["compared"]) == {"mean_abs_gap", "max_abs_gap",
                                    "route_mismatch"}
    assert res["compared"]["route_mismatch"]["value"] == 0
    assert res["correct"], res["compared"]


def test_program_matches_reference_logits_and_routes():
    """The timed forward's logits and expert ids against the reference's
    own routing, with no tie taken from the program."""
    wl, cfg = tiny_cell()
    entry = run.load_module("entries", wl["entry"])
    state = entry.setup(cfg, wl, 3, jax.devices()[:1])
    entry.call(state, 0, run.call_seed(3, 0))
    tokens, logits, ids = state["kept"]
    from bench.reference import moonlight_analog as ref

    x, routing = ref.final_hidden(state["params"], tokens, state["m"],
                                  state["a"], "highest")
    np.testing.assert_array_equal(
        np.sort(routing["ids"], -1), np.sort(np.asarray(ids), -1))
    want = ref.head(state["params"], x, state["m"], state["a"], "highest")
    got = np.asarray(logits).reshape(-1, logits.shape[-1])
    np.testing.assert_allclose(got, np.asarray(want(0, got.shape[0])),
                               rtol=0, atol=2e-5 * np.abs(got).max())


def test_high_precision_control_reads_far_above_a_sound_run():
    sound = run_tiny(seed=7)["compared"]
    wl, cfg = tiny_cell()
    entry = run.load_module("entries", wl["entry"])
    state = entry.setup(cfg, wl, 7, jax.devices()[:1])
    rec = entry.call(state, 0, run.call_seed(7, 0))
    readings = entry.control(state, [rec], 7)
    for name in ("mean_abs_gap", "max_abs_gap"):
        assert readings[name] > 10 * sound[name]["value"], (name, readings)


def _patch_grouped(monkeypatch, alter):
    """Alter every expert product where the grouped kernel produces it."""
    from repro.imc import model_analog

    orig = model_analog._fake_grouped_body

    def patched(x, w, sizes, scal, **kw):
        return alter(orig(x, w, sizes, scal, **kw), sizes)

    monkeypatch.setattr(model_analog, "_fake_grouped_body", patched)


def test_expert_rows_left_out_fail(monkeypatch, fresh_forward):
    def dropped(y, sizes):
        # the last expert's rows read zero: a dispatch that drops tokens
        end = jnp.cumsum(sizes)
        rows = jnp.arange(y.shape[0])[:, None]
        return jnp.where((rows >= end[-1] - sizes[-1]) & (rows < end[-1]),
                         0.0, y)

    _patch_grouped(monkeypatch, dropped)
    assert not run_tiny()["correct"]


def test_expert_parallel_shares_add_up_to_the_whole_layer():
    """Four chips' disjoint expert ranges, run by the program's MoE layer
    under the fake-analog hooks, with the shared experts counted once, add
    up to the uncut reference layer."""
    import dataclasses
    from bench.entries import analog_forward_moe as entry
    from bench.reference import moonlight_analog as ref
    from repro.circuit.bitline import BitlineParams
    from repro.imc import model_analog
    from repro.models.common import intercept_linears
    from repro.models.ffn import dense_ffn, moe_ffn_dropless

    wl, cfg = tiny_cell(held_first=0)
    cfg["n_routed_experts"] = 8                    # the uncut layer
    m = entry.model_dims(cfg)
    params = entry.make_params(m, 5)
    p = jax.tree_util.tree_map(lambda t: t[0], params["blocks"]["pos0"])
    a = entry.dense.analog_consts(cfg)
    h2 = jax.random.normal(jax.random.PRNGKey(2), (128, m["d_model"]))
    with jax.default_matmul_precision("highest"):
        routed, shared = ref.moe_layer(p, h2, m, a, "highest")
    want = np.asarray(routed + shared)

    arch = entry._program_config(cfg, m)
    acfg = entry.dense._analog_config(cfg)
    scal = model_analog._fake_scalars("afmtj", acfg, BitlineParams(), 1.0,
                                      None)
    flags = dict(adc_bits=0, apply_fet=False, use_fail=False, ir_drop=True,
                 has_imax=False, decode=True, interpret=True)

    def hook(x2, w, tag):
        return model_analog._fake_mvm_body(
            x2, w, BitlineParams(rows=w.shape[0]), scal, **flags)

    grouped = functools.partial(model_analog._fake_grouped_body, scal=scal,
                                **flags)
    @functools.partial(jax.jit, static_argnames="chip")
    def layer_share(ffn, h, chip):
        # each chip's routed part; the shared experts, which every chip
        # computes alike, are counted once below
        share = dataclasses.replace(arch, moe=dataclasses.replace(
            arch.moe, held=(2 * chip, 2), shared_expert=False))
        ffn = dict(ffn, **{k: ffn[k][2 * chip:2 * chip + 2]
                           for k in ("w_gate", "w_up", "w_down")})
        with intercept_linears(hook, lambda x, w, s, t: grouped(x, w, s)):
            return moe_ffn_dropless(ffn, h[None], share)[0]

    @jax.jit
    def shared_part(ffn, h):
        with intercept_linears(hook):
            return dense_ffn(ffn["shared"], h, arch, "shared_")

    with jax.default_matmul_precision("highest"):
        total = shared_part(p["ffn"], h2)
        for chip in range(4):
            total = total + layer_share(p["ffn"], h2, chip)
    np.testing.assert_allclose(np.asarray(total), want, rtol=0,
                               atol=1e-5 * np.abs(want).max())


def test_counted_work_matches_the_program_forward():
    """``counting_moe``'s linear and expert FLOPs are the products the
    program's forward makes: every ``linear`` and ``grouped_linear`` call
    recorded under the hooks, the expert rows as routed."""
    from bench import counting_moe
    from bench.entries import analog_forward_moe as entry
    from repro.imc.model_analog import _forward_unrolled
    from repro.models.common import intercept_linears

    _, cfg = tiny_cell()
    m = entry.model_dims(cfg)
    params = entry.make_params(m, 1)
    arch = entry._program_config(cfg, m)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, m["vocab"])
    flops = {"linear": [], "grouped": []}
    routed = []

    def hook(x2, w, tag):
        flops["linear"].append(2.0 * x2.shape[0] * w.shape[0] * w.shape[1])
        return x2 @ w

    def grouped(x, w, sizes, tag):
        rows = int(jnp.sum(sizes))
        routed.append(rows)
        flops["grouped"].append(2.0 * rows * w.shape[1] * w.shape[2])
        return jax.lax.ragged_dot(x, w, sizes)

    with intercept_linears(hook, grouped):
        _forward_unrolled(params, arch, tokens)
    assert len(routed) == 3 * (m["n_layers"] - m["first_k_dense"])
    rows = routed[::3]                       # gate, up, down share rows
    linear = sum(2.0 * a * k * n
                 for a, k, n in counting_moe.dense_linear_shapes(m, 32))
    assert sum(flops["linear"]) == linear
    attn = counting_moe.forward_flops(m, 2, 16, 0) - linear
    assert (sum(flops["linear"]) + sum(flops["grouped"]) + attn
            == counting_moe.forward_flops(m, 2, 16, sum(rows)))
    ops, _ = counting_moe.grouped_window_work([[[r] for r in rows]], m)
    assert ops == sum(flops["grouped"])


def test_readers_read_nothing_without_the_expert_kernels():
    """On a program without the fake-analog kernels (the parent commit) the
    roofline readers return nothing rather than raise."""
    from bench import run as bench_run

    class Trace:
        n_devices, idle_share = 1, 0.25

        def kernel_seconds(self, match):
            return 0.0

    ctx = {"trace": Trace(), "work": {}, "calls": 3, "span_s": 10.0,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    for name in ("fake_analog_moe_roofline", "fake_analog_dense_roofline"):
        assert bench_run.load_module("metrics", name).read(ctx) is None


def test_roofline_readers_split_the_kernels_by_name():
    """The grouped reader takes the expert kernels, the dense reader every
    other custom call; neither takes an op that only reads a kernel's
    output."""
    from bench import run as bench_run

    moe = bench_run.load_module("metrics", "fake_analog_moe_roofline")
    dense = bench_run.load_module("metrics", "fake_analog_dense_roofline")
    call = 'custom_call_target="tpu_custom_call"'
    texts = {"grouped": f"fake_analog_moe_w_up.12 {call}",
             "dense": f"fake_analog_wkv_b.3 {call}",
             "consumer": "fusion.7 f32[4096,1408] fake_analog_moe_w_gate.11"}
    assert [k for k, t in texts.items() if moe.is_kernel(t)] == ["grouped"]
    assert [k for k, t in texts.items() if dense.is_kernel(t)] == ["dense"]


def test_balanced_bias_evens_the_reference_loads():
    """The selection bias the set-up gives the cell comes from the plain
    reference alone, and under it the reference's own routes load every
    routed expert evenly over the balancing batches, layer by layer."""
    from bench.reference import moonlight_analog as ref

    wl, cfg = tiny_cell()
    entry = run.load_module("entries", wl["entry"])
    m = entry.model_dims(cfg)
    state = {"t": wl["traffic"], "m": m, "a": entry.dense.analog_consts(cfg),
             "params": entry.make_params(m, 9)}
    entry.balance_router(state, 9)
    key = jax.random.PRNGKey(9)
    t = wl["traffic"]
    ids = []
    for i in range(entry.BALANCE_BATCHES):
        tokens = jax.random.randint(jax.random.fold_in(key, i),
                                    (t["batch"], t["seq"]), 0, m["vocab"])
        _, routing = ref.final_hidden(state["params"], tokens, m, state["a"],
                                      "highest")
        ids.append(routing["ids"])
    for layer in np.concatenate(ids, axis=1):
        load = np.bincount(layer.ravel(), minlength=m["n_experts"])
        assert np.abs(load / load.mean() - 1).max() < 0.1, load

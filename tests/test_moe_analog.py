"""Expert layers on the analog path: the grouped fake-analog kernel, the
dropless dispatch under the interception hooks, and an expert-parallel
share of moonlight-16b-a3b (latent attention + sigmoid-routed experts)
through ``analog_model_logits``."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.circuit.bitline import BitlineParams
from repro.configs.registry import smoke_config
from repro.imc import model_analog as ma
from repro.imc.analog_pipeline import AnalogConfig
from repro.kernels.fake_analog import (AUX_ROWS, fake_analog_grouped_pallas,
                                       fake_analog_mac_pallas)
from repro.models import model as M
from repro.models.common import grouped_linear, intercept_linears


@pytest.mark.parametrize("sizes", [(130, 0, 70), (1, 255, 129, 3),
                                   (300, 0, 520, 10), (600, 1, 700)])
def test_grouped_kernel_matches_per_group_calls(sizes):
    """Rows of group g read crossbar g exactly as one ``fake_analog`` call
    per group would (bit for bit), at row tiles of 128, 256 and 512; empty
    groups and rows past the total are handled, and rows past the total
    read zero."""
    rng = np.random.default_rng(len(sizes))
    g, k, n = len(sizes), 200, 150
    m = sum(sizes) + 37
    v = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    wn = jnp.asarray(rng.uniform(-1, 1, size=(g, k, n)), jnp.float32)
    fail = jnp.asarray(rng.integers(0, 4, size=(g, k, n)), jnp.float32)
    aux = jnp.asarray(rng.uniform(0.5, 1.5, size=(g, AUX_ROWS, n)),
                      jnp.float32)
    kw = dict(adc_bits=6, use_fail=True, interpret=True)
    out = jax.jit(functools.partial(fake_analog_grouped_pallas, **kw))(
        v, wn, fail, aux, jnp.asarray(sizes, jnp.int32))
    start = 0
    for i, size in enumerate(sizes):
        if size:
            want = fake_analog_mac_pallas(v[start:start + size], wn[i],
                                          fail[i], aux[i], **kw)
            np.testing.assert_array_equal(np.asarray(out[start:start + size]),
                                          np.asarray(want))
        start += size
    assert not np.asarray(out[start:]).any()


def _share_config(held=(1, 2)):
    cfg = smoke_config("moonlight-16b-a3b")
    return dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe,
                                                            held=held))


@pytest.fixture(scope="module")
def share_state():
    cfg = _share_config()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    params["blocks"]["pos0"]["ffn"]["router_bias"] = 0.05 * jax.random.normal(
        jax.random.PRNGKey(4), (cfg.n_pattern_repeats, cfg.moe.num_experts))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    return cfg, params, tokens


def _walk(jaxpr, dots, kernels):
    """The dot_generals outside Pallas kernels, and the kernels' names."""
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            kernels.append(str(e.params.get("name_and_src_info",
                                            e.params.get("name"))).split()[0])
            continue
        if e.primitive.name == "dot_general":
            dots.append(e)
        for val in e.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _walk(sub, dots, kernels)
    return dots, kernels


def test_no_expert_product_outside_the_kernels(share_state):
    """Under the fake-analog hooks every linear of the expert-parallel
    share runs in a named kernel: MLA projections, the router, each held
    expert (grouped), the shared experts, the dense layer and the head.
    The only dot_generals left are attention's score and value products."""
    cfg, params, tokens = share_state
    fn = ma._jitted_fake_forward(cfg, 0, False, False, True, True)
    scal = ma._fake_scalars("afmtj", AnalogConfig(adc_bits=0),
                            BitlineParams(), 1.0, None)
    dots, kernels = _walk(jax.make_jaxpr(fn)(params, tokens, scal).jaxpr,
                          [], [])
    assert len(dots) == 2 * cfg.n_layers
    assert all(e.params["dimension_numbers"][1][0] for e in dots)  # batched
    n_moe = cfg.n_layers - cfg.first_k_dense
    for site in ("wq", "wkv_a", "wkv_b", "wo"):
        assert kernels.count(f"fake_analog_{site}") == cfg.n_layers
    for site in ("router", "moe_w_gate", "moe_w_up", "moe_w_down",
                 "shared_w_gate", "shared_w_up", "shared_w_down"):
        assert kernels.count(f"fake_analog_{site}") == n_moe, site
    for site in ("w_gate", "w_up", "w_down"):
        assert kernels.count(f"fake_analog_{site}") == cfg.first_k_dense
    assert kernels.count("fake_analog_unembed") == 1


def test_routes_come_back_with_the_logits(share_state):
    """``with_routes``: each MoE layer's ids over all routed experts and the
    rows routed to each held expert, consistent with each other."""
    cfg, params, tokens = share_state
    logits, routes = ma.analog_model_logits(
        params, cfg, tokens, AnalogConfig(adc_bits=0), with_routes=True)
    n_moe = cfg.n_layers - cfg.first_k_dense
    ids, sizes = np.asarray(routes["ids"]), np.asarray(routes["sizes"])
    assert logits.shape == tokens.shape + (cfg.vocab,)
    assert ids.shape == (n_moe, tokens.size, cfg.moe.top_k)
    lo, n = cfg.moe.held
    want = np.stack([np.bincount(layer.ravel(), minlength=4)[lo:lo + n]
                     for layer in ids])
    np.testing.assert_array_equal(sizes, want)


def test_linear_hook_alone_sees_every_expert():
    """With only a ``linear`` hook installed (the device and bnn modes),
    ``grouped_linear`` sends each group's rows through it as their own
    product, and the result is the grouped product."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(10, 4)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 4, 5)), jnp.float32)
    sizes = jnp.asarray([4, 0, 3], jnp.int32)
    seen = []

    def hook(x2, w2, tag):
        seen.append(tag)
        return x2 @ w2

    with intercept_linears(hook):
        y = grouped_linear(x, w, sizes, "moe_w_up")
    assert seen == ["moe_w_up"] * 3
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(jax.lax.ragged_dot(x, w, sizes)),
                               rtol=1e-6, atol=1e-6)

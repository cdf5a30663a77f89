"""Work counted from shapes: the closed-form model FLOPs against the jaxpr
dot-FLOP walk of the reference forward, and the fake-analog roofline."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from bench import counting, run  # noqa: E402
from bench.reference import qwen2_analog as ref  # noqa: E402


def test_forward_flops_match_the_jaxpr_walk_of_the_reference():
    entry = run.load_module("entries", "analog_forward")
    _, cfg = run.load_cell("qwen2-0.5b.analog_eval")
    cfg["model"].update(hidden_size=64, intermediate_size=96,
                        num_hidden_layers=2, num_attention_heads=4,
                        num_key_value_heads=2, vocab_size=160)
    m, a = entry.model_dims(cfg), entry.analog_consts(cfg)
    batch, seq = 2, 16
    params = jax.eval_shape(lambda: entry.make_params(m, 0))
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    hp = "highest"

    def forward(p, t):
        x = ref.final_hidden(p, t, m, a, hp)
        return ref.head(p, x, m, a, hp)(0, batch * seq)

    walked = counting.audit_flops(forward, params, tokens)
    assert walked == counting.decoder_forward_flops(m, batch, seq)


def test_fake_analog_work_and_roofline():
    ops, nbytes = counting.fake_analog_work([(4096, 896, 4864)])
    assert ops == 2.0 * 4096 * 896 * 4864
    assert nbytes == 4 * (4096 * 896 + 2 * 896 * 4864 + 8 * 4864
                          + 4096 * 4864)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = counting.roofline_seconds(ops, nbytes, peaks)
    assert bound == "compute" and t == ops / 197e12
    t, bound = counting.roofline_seconds(1.0, 1e9, peaks)
    assert bound == "memory" and t == 1e9 / 819e9


def test_linear_shapes_of_qwen2_0_5b():
    entry = run.load_module("entries", "analog_forward")
    _, cfg = run.load_cell("qwen2-0.5b.analog_eval")
    m = entry.model_dims(cfg)
    shapes = counting.decoder_linear_shapes(m, 4096)
    assert len(shapes) == 24 * 7 + 1
    assert shapes[-1] == (4096, 896, 151936)
    params = sum(k * n for _, k, n in shapes)
    assert abs(params - 494e6) / 494e6 < 0.01   # qwen2-0.5b, tied head

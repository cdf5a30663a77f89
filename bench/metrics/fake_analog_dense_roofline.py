"""Share of its roofline that the one-crossbar fake-analog kernel reaches
in the expert-layer forward: the least time the chip needs for the
window's calls of every linear that is not a routed expert (latent
attention, the leading dense FFN, the router, the shared experts and the
head; operations over the bf16 peak, or bytes over the HBM bandwidth,
whichever is larger; counted from the calls' shapes by
``bench.counting_moe``) over those kernels' summed device time.  The
kernels are the custom calls not named ``fake_analog_moe_*``; a program
that cannot run the cell reads nothing."""
from bench import counting

UNIT = "%"


def is_kernel(text: str) -> bool:
    return "tpu_custom_call" in text and "fake_analog_moe_" not in text


def read(ctx):
    t = ctx["trace"].kernel_seconds(is_kernel)
    shapes = ctx["work"].get("fake_analog_dense_shapes")
    if t <= 0.0 or not shapes:
        return None
    ops, nbytes = counting.fake_analog_work(shapes)
    bound, _ = counting.roofline_seconds(ops * ctx["calls"],
                                         nbytes * ctx["calls"], ctx["peaks"])
    return 100.0 * bound / t

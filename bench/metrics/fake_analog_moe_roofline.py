"""Share of its roofline that the grouped fake-analog kernel (the expert
products) reaches: the least time the chip needs for the window's grouped
calls (operations over the bf16 peak, or bytes over the HBM bandwidth,
whichever is larger; counted by ``bench.counting_moe`` from the rows each
held expert was routed in each forward) over the kernels' summed device
time.  The kernels are the custom calls named ``fake_analog_moe_w_gate``,
``_w_up`` and ``_w_down``; a program without them reads nothing."""
from bench import counting

UNIT = "%"


def is_kernel(text: str) -> bool:
    return "tpu_custom_call" in text and "fake_analog_moe_" in text


def read(ctx):
    t = ctx["trace"].kernel_seconds(is_kernel)
    work = ctx["work"].get("fake_analog_moe_window")
    if t <= 0.0 or not work:
        return None
    bound, _ = counting.roofline_seconds(work[0], work[1], ctx["peaks"])
    return 100.0 * bound / t

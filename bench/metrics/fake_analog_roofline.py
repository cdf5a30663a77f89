"""Share of its roofline that the fake-analog kernel reaches: the least
time the chip needs for the window's kernel calls (operations over the
bf16 peak, or bytes over the HBM bandwidth, whichever is larger; counted
from the calls' shapes by ``bench.counting``) over the kernel's summed
device time."""
from bench import counting

UNIT = "%"


def is_kernel(text: str) -> bool:
    """The fake-analog Pallas kernel: the forward's only custom calls."""
    return 'tpu_custom_call' in text


def read(ctx):
    t = ctx["trace"].kernel_seconds(is_kernel)
    if t <= 0.0:
        return None
    ops, nbytes = counting.fake_analog_work(ctx["work"]["fake_analog_shapes"])
    bound, _ = counting.roofline_seconds(ops * ctx["calls"],
                                         nbytes * ctx["calls"], ctx["peaks"])
    return 100.0 * bound / t

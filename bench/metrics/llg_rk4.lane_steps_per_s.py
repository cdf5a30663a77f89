"""Lane-steps per second of device time in the thermal LLG kernel: the
window's lanes times the grid's fixed horizon (the same work whatever
implements it), over the summed device time of the kernel's events on all
chips."""
UNIT = "lane-steps/s"


def is_kernel(text: str) -> bool:
    """The thermal LLG Pallas kernel: the one custom call of the campaign's
    launch, and the only one that takes per-lane uint32 stream seeds."""
    return 'tpu_custom_call' in text and 'u32[1,' in text


def read(ctx):
    t = ctx["trace"].kernel_seconds(is_kernel)
    if t <= 0.0:
        return None
    return ctx["work"]["lane_steps"] * ctx["calls"] / t

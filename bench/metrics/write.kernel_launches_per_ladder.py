"""Thermal LLG kernel launches per write-verify ladder: the kernel's events
in the traced window over the ladders completed in it.  A count."""
UNIT = "launches"


def is_kernel(text: str) -> bool:
    """The thermal LLG Pallas kernel: the only custom call of the ladder's
    launches that takes per-lane uint32 stream seeds."""
    return 'tpu_custom_call' in text and 'u32[1,' in text


def read(ctx):
    n = ctx["trace"].kernel_count(is_kernel)
    if n == 0:
        return None
    return n / ctx["calls"]

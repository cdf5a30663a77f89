"""Model FLOPs of the window's forwards (every linear with the output
head, and attention's score and value products, counted from the
configuration by ``bench.counting``) over the traced window times the
chips' bf16 peak."""
UNIT = "%"


def read(ctx):
    flops = ctx["work"]["model_flops"] * ctx["calls"]
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["trace"].n_devices
    return 100.0 * flops / (ctx["span_s"] * peak)

"""The whole step's share of the chips' peak: the operations the
window's work REQUIRES (``lib.flops``; no recomputation, no padding, no
masked attention) over window time, chips and peak bf16 FLOP/s."""


def read(ctx):
    if not ctx.get("required_flops") or not ctx.get("window_s"):
        return None
    return 100.0 * ctx["required_flops"] / (
        ctx["window_s"] * ctx["chips"] * ctx["peaks"][0])

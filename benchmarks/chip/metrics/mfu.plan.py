"""Whole sampling path: operations the window's programs need (encoder
forward over distinct graphs plus the K-sweep, the roofline counts) over
the window's host time, as a share of the chips' bf16 peak."""


def read(view):
    c = view["counts"]
    flops = c.get("encode_flops", 0.0) + c.get("sweep_flops", 0.0)
    if flops <= 0:
        return None
    peak = view["peak"]["flops_per_s"] * view["chips"]
    return 100.0 * flops / view["window"]["elapsed_s"] / peak

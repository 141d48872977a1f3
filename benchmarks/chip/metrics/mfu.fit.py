"""Contrastive fit: operations of every step (two views, encoder and
projection head, InfoNCE, backward at twice the forward, on the real nodes
and edges) and each fit's held-out forward, over the window's host time,
as a share of the chips' bf16 peak."""


def read(view):
    flops = view["counts"].get("work_flops", 0.0)
    if flops <= 0:
        return None
    peak = view["peak"]["flops_per_s"] * view["chips"]
    return 100.0 * flops / view["window"]["elapsed_s"] / peak

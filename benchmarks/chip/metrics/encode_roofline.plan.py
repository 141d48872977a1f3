"""Encoder forward: least time for its operations and bytes at the chip's
peaks, over the device time of the embed executable in the trace."""

from benchmarks.chip.counts import least_time_s

#: the method's packed encode, jitted from a lambda
MODULE = "jit__lambda"


def read(view):
    dev = view["trace"]["module_s"].get(MODULE, 0.0)
    c = view["counts"]
    if dev <= 0 or not c.get("encode_flops"):
        return None
    return 100.0 * least_time_s(c["encode_flops"], c["encode_bytes"],
                                view["peak"]) / dev

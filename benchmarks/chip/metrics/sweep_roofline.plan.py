"""K-sweep (Lloyd steps for every candidate K plus the blocked silhouette):
least time for its operations and bytes at the chip's peaks, over the
device time of the sweep executable in the trace."""

from benchmarks.chip.counts import least_time_s

#: the compiled K-sweep: jitted from a functools.partial, which the
#: profiler names "_unknown"
MODULE = "jit__unknown"


def read(view):
    dev = view["trace"]["module_s"].get(MODULE, 0.0)
    c = view["counts"]
    if dev <= 0 or not c.get("sweep_flops"):
        return None
    return 100.0 * least_time_s(c["sweep_flops"], c["sweep_bytes"],
                                view["peak"]) / dev

"""Host time packing the fit's epoch and staging its scan chunks over the
window: ``fit.plan_epoch`` plus ``fit.pack``, the slicing, edge-padding and
upload of each chunk's rows on the prefetch thread.  The chunk's key
derivation (``fit.keys``, eager device ops that can wait behind the running
chunk) is not host work and is left out."""

from benchmarks.chip.program_spans import window_share


def read(view):
    return window_share(view, ("fit.plan_epoch", "fit.pack"))

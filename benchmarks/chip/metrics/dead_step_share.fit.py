"""Share of the scan steps the device computed that were padding: 1 - live
steps over computed steps, summed over the window's ``fit.chunk`` spans
(each chunk computes its whole length; masked steps are discarded)."""

from benchmarks.chip.program_spans import recorded


def read(view):
    recs = recorded()
    if recs is None:
        return None
    chunks = [r.counts for r in recs if r.name == "fit.chunk"]
    computed = sum(c["computed"] for c in chunks)
    if computed == 0:
        return None
    return 100.0 * (1.0 - sum(c["live"] for c in chunks) / computed)

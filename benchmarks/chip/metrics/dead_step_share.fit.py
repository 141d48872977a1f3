"""Share of the scan steps the device computed that were padding: 1 - live
steps over computed steps, summed over the window's ``fit.chunk`` spans.
A chunk whose body branches past its padded steps counts them as
``skipped``, not ``computed``, and reads 0; one that computes them and
discards the result reads their share."""

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

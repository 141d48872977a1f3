"""Share of the points the K-sweep's dispatches compute that are padding:
1 - Σ ``points`` / Σ ``padded`` over the window's ``plan.sweep`` spans
(``padded`` is the dispatch's pow2 batch bucket times its pow2 points
bucket).  A program without the ``padded`` count reads nothing."""

from benchmarks.chip.program_spans import recorded


def read(view):
    recs = recorded()
    if recs is None:
        return None
    sweeps = [r.counts for r in recs if r.name == "plan.sweep"]
    padded = sum(c.get("padded", 0) for c in sweeps)
    if not sweeps or any("padded" not in c for c in sweeps) or padded == 0:
        return None
    return 100.0 * (1.0 - sum(c["points"] for c in sweeps) / padded)

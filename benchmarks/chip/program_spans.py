"""The program's own spans (``repro.telemetry``), for the per-layer metrics
that read them.

The program records spans only while a profiler session is active, and the
harness opens one around the measured window of a ``--trace 1`` run, so
after ``stop_trace`` the records are the window's.  A program without the
recorder, a run that recorded nothing, or a window whose spans overflowed
the recorder's buffer gives ``None``: the metric is then left out of the
result line, rather than read too low.
"""

from __future__ import annotations


def recorded():
    """The window's span records, or None where there are none or some
    were dropped."""
    try:
        from repro.telemetry import dropped, spans
    except ImportError:  # a program that predates the recorder
        return None
    if dropped():
        return None
    return spans() or None


def window_share(view, names) -> float | None:
    """Summed duration of the spans named ``names`` over the window's host
    time, in %.  Spans on other threads overlap the main thread, so the
    share of one layer is its host time, not the window's critical path."""
    recs = recorded()
    if recs is None:
        return None
    busy = sum(r.end_ns - r.start_ns for r in recs if r.name in names)
    return 100.0 * busy / 1e9 / view["window"]["elapsed_s"]

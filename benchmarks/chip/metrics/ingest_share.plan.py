"""Host time tracing kernels and building their graphs (``ingest.build``
spans: kernels actually traced, not memo or store hits) over the window."""

from benchmarks.chip.program_spans import window_share


def read(view):
    return window_share(view, ("ingest.build",))

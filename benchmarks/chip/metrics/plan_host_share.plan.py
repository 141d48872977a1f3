"""Host time of planning around the K-sweep: kmeans++ seeding, K selection
from the swept scores and plan building (``plan.seed`` + ``plan.select`` +
``plan.build`` spans) over the window."""

from benchmarks.chip.program_spans import window_share


def read(view):
    return window_share(view, ("plan.seed", "plan.select", "plan.build"))

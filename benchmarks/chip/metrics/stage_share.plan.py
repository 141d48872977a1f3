"""Host time packing embed micro-batches and uploading them to the device
(``embed.pack`` + ``embed.upload`` spans) over the window."""

from benchmarks.chip.program_spans import window_share


def read(view):
    return window_share(view, ("embed.pack", "embed.upload"))

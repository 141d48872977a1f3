"""Closed loop over a model-zoo configuration's programs, as ``closed_suite``
runs the paper suite: whole passes, each program through ``run_prepare``
with the embedding cache and the ingest memo emptied, then ``plan_batch``.

Traffic keys: ``generator`` ("closed_zoo").  Configuration keys besides
``closed_suite``'s: ``kernel_reference``, the module under
``benchmarks/chip`` that holds the architecture's plain reference forward
(``reference_dsv3`` for DeepSeek-V3), ``context`` and ``decode_steps``.

The comparison is ``closed_suite``'s, and one more number checks the trace
path itself: ``kernel_mismatches``, the matrix products of each program's
launch stream (read off its kernels' parameters, the reference's
``stream_products``) that differ from those of ``decode_steps`` decode
steps of the reference forward at the configuration's published widths and
``context`` (the reference's ``products``).
"""

from __future__ import annotations

import importlib

from benchmarks.chip.generators import closed_suite


class Generator(closed_suite.Generator):

    def kernel_mismatches(self) -> int:
        c = self.cfg
        ref = importlib.import_module(
            f"benchmarks.chip.{c['kernel_reference']}")
        want = ref.products(c, c["context"]) * c["decode_steps"]
        return sum(ref.mismatches(
            ref.stream_products(
                [(k.name, k.template, k.params) for k in prog.kernels], c),
            want) for prog in self.programs)

    def _gaps(self, outputs: dict, log: bool = False) -> dict:
        """``closed_suite``'s numbers and ``kernel_mismatches``."""
        return dict(super()._gaps(outputs, log),
                    kernel_mismatches=float(self.kernel_mismatches()))

"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

What it relies on, as the TPU runtime writes it:

- device planes are named ``/device:TPU:<n>``; their line ``XLA Modules``
  holds one event per executable run, named ``<module>(<program id>)``
  (``jit_<function>``; a jitted lambda is ``jit__lambda``);
- host planes are named ``/host:...``; a ``jax.profiler.TraceAnnotation``
  shows there as an event with the annotation's name, on the same clock.

The harness annotates the measured window (``WINDOW``) and each call into
the program (its spans).  From those this module gives the device busy time
(the union of executable runs, clipped to the window, averaged over the
devices), the device time of each executable by module name, the longest
idle gaps with the span the host was in for most of each, and the executables that took
most device time.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

WINDOW = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINE = "XLA Modules"
_PROGRAM_ID = re.compile(r"\(\d+\)$")
#: an idle gap mostly outside every span of the harness
OUTSIDE = "host outside any span"


def module_base(name: str) -> str:
    """``jit_step(123)`` -> ``jit_step``."""
    return _PROGRAM_ID.sub("", name)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce_trace(path: str, span_names=(), top: int = 10) -> dict:
    """Returns ``{"window_s", "busy_s", "devices", "busy_by_device",
    "module_s", "device_ops", "idle_gaps"}``; times in seconds.  Raises
    when the trace holds no TPU device plane or no window annotation."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    window = None
    spans = []
    modules = defaultdict(list)      # device -> [(start, end, name)]
    wanted = set(span_names)
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    for ev in line.events:
                        modules[dev].append((ev.start_ns,
                                             ev.start_ns + ev.duration_ns,
                                             module_base(ev.name)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name in wanted:
                        spans.append((ev.start_ns,
                                      ev.start_ns + ev.duration_ns, ev.name))
    if window is None:
        raise ValueError(f"no '{WINDOW}' annotation in {path}")
    if not modules:
        raise ValueError(f"no TPU device plane with '{MODULE_LINE}' in {path}")
    lo, hi = window
    busy_by_dev, module_ns = {}, defaultdict(float)
    unions = {}
    for dev, evs in sorted(modules.items()):
        clipped = [(max(s, lo), min(e, hi), n) for s, e, n in evs
                   if e > lo and s < hi]
        for s, e, n in clipped:
            module_ns[n] += e - s
        unions[dev] = _union([(s, e) for s, e, _ in clipped])
        busy_by_dev[dev] = sum(e - s for s, e in unions[dev]) / 1e9
    n_dev = len(modules)
    first = min(unions)
    gaps = []
    prev = lo
    for s, e in unions[first] + [[hi, hi]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    named = []
    for gs, ge in gaps:
        cover = defaultdict(int)
        for ss, se, name in spans:
            cover[name] += max(0, min(ge, se) - max(gs, ss))
        cover[OUTSIDE] = (ge - gs) - sum(cover.values())
        named.append([max(cover, key=cover.get), (ge - gs) / 1e9])
    named.sort(key=lambda x: -x[1])
    module_s = {k: v / 1e9 / n_dev for k, v in module_ns.items()}
    dev_ops = sorted(module_s.items(), key=lambda x: -x[1])[:top]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_by_dev.values()) / n_dev,
        "devices": n_dev,
        "busy_by_device": busy_by_dev,
        "module_s": module_s,
        "device_ops": [[k, v] for k, v in dev_ops],
        "idle_gaps": named[:top],
    }

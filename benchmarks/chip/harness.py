"""Shared pieces of the on-chip benchmark: files found by name, the device
gate, host spans, compile counting, the comparison records."""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import sys
import time
from collections import defaultdict
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(BENCH_DIR))
SRC = os.path.join(REPO, "src")


def ensure_paths() -> None:
    """The system under test lives in ``src/``; the benchmark imports as
    ``benchmarks.chip``."""
    for p in (SRC, REPO):
        if p not in sys.path:
            sys.path.insert(0, p)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def bench_file() -> dict:
    return load_json(os.path.join(REPO, "BENCHMARK.json"))


@dataclass
class Cell:
    """One workload of BENCHMARK.json with the files it names."""
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list


def _applies(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") in e2e_names if "moves" in metric else True


def load_cell(name: str, bench: dict | None = None) -> Cell:
    bench = bench or bench_file()
    wl = {w["name"]: w for w in bench["workloads"]}
    if name not in wl:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(wl)}")
    w = wl[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, set())]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _applies(m, name, e2e_names)]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_json(os.path.join(REPO, conf["file"])),
        traffic=load_json(os.path.join(BENCH_DIR, "traffic",
                                       f"{w['traffic']}.json")),
        limits=load_json(os.path.join(BENCH_DIR, "limits", f"{name}.json")),
        end_to_end=e2e, per_layer=layer)


def load_generator(kind: str):
    """A traffic file names its generator; each is a module of ``generators``."""
    return importlib.import_module(f"benchmarks.chip.generators.{kind}").Generator


def load_reader(metric: str):
    """``metrics/<metric>.py`` defines ``read(view) -> float | None``."""
    path = os.path.join(BENCH_DIR, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> dict:
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"peaks.json")
    return table[device_kind]


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def device_gate(min_count: int) -> dict:
    """Refuse to run anywhere but on a TPU with compiled Pallas kernels and
    at least ``min_count`` devices."""
    import jax

    from repro.kernels import default_interpret

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log(f"device platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    if dev["platform"] != "tpu":
        raise SystemExit(f"no TPU: JAX found {dev['platform']} devices")
    if default_interpret():
        raise SystemExit("Pallas would run in interpret mode")
    if dev["count"] < min_count:
        raise SystemExit(f"need {min_count} TPU devices, found {dev['count']}")
    return dev


def memory_peak_bytes(n: int) -> int:
    """Peak bytes in use on the fullest of the first ``n`` devices (0 where
    the backend keeps no statistics)."""
    import jax

    peak = 0
    for d in jax.devices()[:n]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class Spans:
    """Host-clock spans around calls into the program, totalled by name.
    With ``annotate`` each span is also written into the profiler's trace."""

    def __init__(self):
        self.totals = defaultdict(float)
        self.annotate = False

    @contextmanager
    def __call__(self, name: str):
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
        else:
            ann = nullcontext()
        t0 = time.perf_counter()
        try:
            with ann:
                yield
        finally:
            self.totals[name] += time.perf_counter() - t0

    def reset(self) -> None:
        self.totals.clear()


class CompileCounter:
    """Executables compiled or loaded, and persistent-cache hits and misses,
    from JAX's own monitoring events; the harness compares snapshots taken
    around the measured window."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_):
        if event.endswith("backend_compile_duration"):
            self.compiles += 1

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> tuple:
        return (self.compiles, self.cache_hits, self.cache_misses)


@dataclass
class Check:
    """One number compared with its limit; passes when value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)


def compared(cell_limits: dict, got: dict) -> tuple:
    """(checks, observed): a Check for each number the cell's limits file
    names, and the other numbers, which are printed and not compared."""
    lim = cell_limits["limits"]
    return ([Check(k, got[k], float(v)) for k, v in lim.items()],
            {k: v for k, v in got.items() if k not in lim})

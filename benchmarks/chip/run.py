"""On-chip benchmark of the GCL-Sampler: one cell, one run, one result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell (BENCHMARK.json ``workloads``) names a configuration file, a
traffic file and, through the traffic's ``generator``, the generator in
``generators/`` that runs it.  A run: persistent compile cache, device gate
(a TPU, compiled Pallas, enough chips: otherwise exit 1 and no result),
set-up that builds inputs and weights from the seed and warms every shape,
the measured window, then the comparison with the plain reference.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` records
a profiler trace of the window and reports the per-layer metrics read by
``metrics/<name>.py``.  The last stdout line is the result JSON; the last
stderr lines are the numbers compared, each with its limit.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

# one thread per numeric library: the host path is one Python thread, and
# thread pools contending on a shared host only add noise between runs
for _var in ("OMP_NUM_THREADS", "OPENBLAS_NUM_THREADS", "MKL_NUM_THREADS"):
    os.environ.setdefault(_var, "1")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.chip import harness  # noqa: E402


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             bench: dict | None = None, gate=harness.device_gate,
             t_start: float = T_START) -> tuple:
    """One run of one cell; returns (result dict, checks)."""
    harness.ensure_paths()
    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    # every executable goes to the persistent cache, so a run after the
    # first compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.load_cell(workload, bench)
    dev = gate(cell.chips)
    harness.log(f"cell {workload} seed {seed} seconds {seconds} trace "
                f"{int(trace)}; compile cache {cache_dir}")
    compiles = harness.CompileCounter()
    spans = harness.Spans()
    generator = harness.load_generator(cell.traffic["generator"])(
        cell=cell, seed=seed, spans=spans)
    generator.setup()
    setup_s = time.perf_counter() - t_start
    harness.log(f"set-up {setup_s!r} s; compiles {compiles.snapshot()} "
                f"(backend compiles, cache hits, cache misses)")

    spans.reset()
    log_dir = None
    if trace:
        log_dir = tempfile.mkdtemp(prefix="bench_trace_")
        spans.annotate = True
        # no Python function tracing: the host path is Python, and tracing
        # each call would slow it several-fold; annotations stay
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    before = compiles.snapshot()
    if trace:
        with jax.profiler.TraceAnnotation("bench.window"):
            window = generator.run_window(seconds)
    else:
        window = generator.run_window(seconds)
    in_window = [a - b for a, b in zip(compiles.snapshot(), before)]
    if trace:
        jax.profiler.stop_trace()
    harness.log(f"window {window!r}")
    harness.log(f"in the window: backend compiles {in_window[0]}, cache "
                f"loads {in_window[1]}, cache misses {in_window[2]}")
    for line in generator.notes():
        harness.log(line)

    device = dict(dev, memory_peak_bytes=harness.memory_peak_bytes(cell.chips))
    result = {"correct": None, "attempted": int(window["attempted"]),
              "failed": int(window["failed"]), "metrics": {},
              "device": device}
    if trace:
        from benchmarks.chip.trace_reduce import find_xplane, reduce_trace

        xplane = find_xplane(log_dir)
        red = reduce_trace(xplane, span_names=generator.span_names)
        shutil.rmtree(log_dir, ignore_errors=True)
        harness.log(f"trace: busy_by_device {red['busy_by_device']}")
        harness.log(f"trace: module_s {red['module_s']}")
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
        view = {"spans": dict(spans.totals), "window": window, "trace": red,
                "peak": harness.peaks(dev["kind"]), "chips": cell.chips,
                "counts": generator.layer_inputs()}
        for m in cell.per_layer:
            value = harness.load_reader(m["name"])(view)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
    else:
        e2e = dict(generator.end_to_end(), setup_s=setup_s)
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}

    generator.free()
    checks = generator.check()
    for name, value in getattr(generator, "observed", {}).items():
        harness.log(f"observed, not compared: {name} = {value!r}")
    result["correct"] = bool(window["failed"] == 0
                             and all(c.ok for c in checks))
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result, checks = run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace))
    except (Exception, SystemExit) as e:  # no result line, non-zero exit
        if not isinstance(e, SystemExit):
            traceback.print_exc()
        print(f"[bench] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    for c in checks:
        print(f"[check] {c.name} = {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

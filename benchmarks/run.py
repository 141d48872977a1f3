"""Benchmark runner — one entry per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run``            fast mode (CI-sized)
``PYTHONPATH=src python -m benchmarks.run --full``     paper-sized runs

Prints ``name,us_per_call,derived`` CSV rows summarizing each benchmark,
and writes detailed JSON under benchmarks/results/.
"""

from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-sized runs (all 11 programs, long training)")
    ap.add_argument("--only", default=None,
                    help="comma list: fig45,table3,fig6,e2e,traincost,"
                         "encode,ingest,plans,serve,roofline")
    args = ap.parse_args()
    fast = not args.full
    only = set(args.only.split(",")) if args.only else None
    if fast:  # keep the paper-sized artifacts (EXPERIMENTS.md inputs) intact
        import os

        os.environ.setdefault("REPRO_RESULTS_SUFFIX", "_fast")

    # fast mode trims the program list to keep CPU runtime sane; --full runs
    # the paper's 11-program suite.
    programs = (
        ["nw", "backprop", "3mm", "bfs", "lud", "AlexNet"] if fast else None
    )

    rows = []

    def bench(name, fn, **kw):
        if only and name not in only:
            return
        t0 = time.time()
        out = fn(**kw)
        dt = time.time() - t0
        derived = _derive(name, out)
        rows.append((name, f"{dt * 1e6:.0f}", derived))
        print(f"[run] {name} done in {dt:.0f}s -> {derived}", flush=True)

    from benchmarks import (
        bench_ablations, bench_accuracy_speedup, bench_crossarch,
        bench_e2e_sim, bench_encode_fusion, bench_ingest, bench_microarch,
        bench_plan_throughput, bench_roofline, bench_serve_latency,
        bench_train_throughput,
    )

    bench("fig45", bench_accuracy_speedup.run, programs=programs, fast=fast)
    bench("table3", bench_crossarch.run, programs=programs, fast=fast)
    bench("fig6", bench_microarch.run, fast=fast)
    bench("e2e", bench_e2e_sim.run,
          programs=("nw", "lud") if fast else bench_e2e_sim.PROGRAMS,
          fast=fast)
    bench("traincost", bench_train_throughput.run, fast=fast)
    bench("encode", bench_encode_fusion.run, fast=fast)
    bench("ingest", bench_ingest.run, fast=fast,
          n_kernels=8 if fast else 32)
    bench("plans", bench_plan_throughput.run, fast=fast)
    bench("serve", bench_serve_latency.run, fast=fast)
    # no scaleout entry: it needs its own process (forced host device
    # count), and a JAX child of this JAX process cannot reach a TPU —
    # run `python -m benchmarks.bench_scaleout` on its own
    if args.full or (only and "ablations" in only):
        bench("ablations", bench_ablations.run, fast=True)
    bench("roofline", bench_roofline.run)

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us},{derived}")


def _derive(name, out) -> str:
    try:
        if name == "fig45":
            s = out["summary"]["GCL-Sampler"]
            return (f"gcl_err={s['avg_error_pct']:.2f}%"
                    f";gcl_speedup={s['avg_speedup']:.1f}x")
        if name == "table3":
            return ";".join(
                f"{p}_err={out['summary'][p]['avg_error_pct']:.2f}%"
                for p in ("P1", "P2", "P3")
            )
        if name == "fig6":
            errs = [v["error_pct"] for prog in out.values() for v in prog.values()]
            return f"max_metric_err={max(errs):.2f}%"
        if name == "e2e":
            sus = [v["sim_speedup"] for v in out.values()]
            return f"max_sim_speedup={max(sus):.1f}x"
        if name == "traincost":
            rates = [v["s_per_100_kernels"] for v in out.values()]
            return f"s_per_100_kernels={max(rates):.1f}"
        if name == "encode":
            return (f"bytes_reduction="
                    f"{out['modelled']['reduction_x']:.2f}x"
                    f";parity={out['parity_max_abs_diff']:.1e}"
                    f";overlap={out['prefetch']['overlap_fraction']:.2f}"
                    f";warm_recompiles={out['warm_recompiles']}")
        if name == "ingest":
            return (f"cold_speedup={out['throughput']['cold_speedup']:.1f}x"
                    f";parity={out['parity_max_abs_diff']:.1e}"
                    f";warm_retraced={out['warm']['retraced']}"
                    f";overlap={out['overlap']['cold_overlap_fraction']:.2f}"
                    f";model_programs={len(out['embed_stream'])}")
        if name == "ablations":
            worst = max(
                r["error_pct"] for prog in out.values() for r in prog.values()
            )
            full_err = max(r["full"]["error_pct"] for r in out.values())
            return f"full_err={full_err:.2f}%;worst_ablation_err={worst:.2f}%"
        if name == "serve":
            return (f"warm_p99_ratio={out['cold_vs_warm']['p99_ratio']:.1f}x"
                    f";batch_speedup="
                    f"{out['batching_speedup_high_load']:.1f}x")
        if name == "roofline":
            n = len(out)
            dom = {}
            for r in out:
                dom[r["dominant"]] = dom.get(r["dominant"], 0) + 1
            return f"cells={n};" + ";".join(f"{k}={v}" for k, v in sorted(dom.items()))
    except Exception as e:  # pragma: no cover
        return f"derive_error={e!r}"
    return ""


if __name__ == "__main__":
    main()

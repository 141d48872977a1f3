"""Plan-serving launcher: continuous-batched sampling-as-a-service.

``python -m repro.launch.plan_serve --load 100,300 --requests 120``

Stands up a :class:`repro.serving.PlanService` (continuous batcher over the
compiled PlanEngine, DESIGN.md §9), optionally pre-warms the executable
pool, and drives it with open-loop Poisson traffic at each offered load,
reporting p50/p99 plan latency, plans/s, queue depth, and batch occupancy.

Knobs: ``--max-delay-ms`` (bucket flush deadline), ``--max-batch``
(programs per compiled dispatch), ``--warmup-buckets 64x16,128x16`` /
``--no-warmup`` (the warm pool), ``--load`` (offered req/s, comma list).

NOT the model-decode server: ``repro.launch.serve`` serves transformer
prefill/decode traffic.  This CLI serves *sampling plans*.  Tenant traffic
with ArtifactStore-backed encoder reuse goes through
``PlanService.submit_program`` (see repro.serving).
"""

from __future__ import annotations

import argparse
import json

from repro.compile_cache import enable_compile_cache
from repro.sampling.engine import bucket_key
from repro.serving import (
    PlanService, parse_buckets, run_open_loop, synthetic_fleet,
)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="repro.launch.plan_serve")
    ap.add_argument("--requests", type=int, default=120,
                    help="requests per offered load")
    ap.add_argument("--load", default="100",
                    help="offered loads in req/s (comma list)")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-delay-ms", type=float, default=5.0)
    ap.add_argument("--warmup-buckets", default=None,
                    help="explicit warm pool, e.g. '64x16,128x16' "
                         "(default: every bucket of the synthetic fleet)")
    ap.add_argument("--no-warmup", action="store_true",
                    help="serve cold: first requests pay the compiles")
    ap.add_argument("--d", type=int, default=16)
    ap.add_argument("--k-max", type=int, default=8)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (fewer requests, one load)")
    ap.add_argument("--json", default=None, help="write results JSON here")
    args = ap.parse_args(argv)
    enable_compile_cache()

    n_requests = min(args.requests, 40) if args.smoke else args.requests
    loads = [float(x) for x in str(args.load).split(",") if x]
    if args.smoke:
        loads = loads[:1]

    fleet = synthetic_fleet(n_requests, d=args.d, seed=args.seed)
    buckets = sorted({bucket_key(r.embeddings) for r in fleet})
    rows = []
    failed = 0
    with PlanService(max_batch=args.max_batch,
                     max_delay_ms=args.max_delay_ms,
                     k_max=args.k_max, iters=args.iters,
                     seed=args.seed) as svc:
        if not args.no_warmup:
            warm = (parse_buckets(args.warmup_buckets)
                    if args.warmup_buckets else buckets)
            built = svc.warmup(warm)
            print(f"[plan-serve] warm pool: {built} executables built for "
                  f"{len(warm)} buckets", flush=True)
        for rate in loads:
            res = run_open_loop(svc, fleet, rate, seed=args.seed)
            s = res.service
            print(
                f"[plan-serve] load {rate:.0f}/s: {res.plans_per_s:.1f} "
                f"plans/s, p50 {res.latency_ms['p50']:.1f}ms, p99 "
                f"{res.latency_ms['p99']:.1f}ms, occupancy "
                f"{s['batch_occupancy'] and round(s['batch_occupancy'], 2)}, "
                f"mean queue {s['mean_queue_depth']:.1f}, flushes "
                f"{s['flush_causes']}", flush=True)
            rows.append(res.to_json())
            failed += res.n_err
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"buckets": [list(b) for b in buckets],
                       "loads": rows}, f, indent=1, sort_keys=True)
        print(f"[plan-serve] wrote {args.json}", flush=True)
    if failed:
        print(f"[plan-serve] {failed} request(s) FAILED", flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())

"""Readings that set a cell's limits: the program on many seeds, its
control, the program's own lower-precision path and planted faults, in one
process.  Not part of a benchmark run.

    python3 benchmarks/chip/control.py --workload zoo-fit --seeds 1,2,3 \
        --control-seeds 4,5,6 --low-policy-seeds 4,5,6 \
        --faults half_batch --fault-seeds 7,8,9 --seconds 5

For each seed: set-up without a warm pass, a short window at the cell's
load, then the same comparison a run makes.  The control is the step below
the precision the configuration states, named by the cell's limits file
(``control``): ``reference_<mode>`` puts the plain reference, with its
matrix products taken in ``<mode>`` (see ``reference._mm``), in the
program's place; ``--precision-controls`` runs the program's K-sweep at
another JAX matmul precision than the configuration's.  Other modes: ``low_policy`` (the program with
its bfloat16 compute policy), ``--reference-modes`` and the faults of
``faults.py``.  One JSON line per reading, then a summary: per number, the
largest sound reading (lower) and the smallest reading of each other mode.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.chip import harness  # noqa: E402

#: the program's own lower-precision path: the bfloat16 compute policy
LOW_POLICY = {"compute_dtype": "bfloat16"}


def control_mode(workload: str, bench=None) -> str:
    """The cell's control, as its limits file names it."""
    return harness.load_cell(workload, bench).limits["control"]


def readings(workload: str, seeds: list, seconds: float, mode: str,
             gate=harness.device_gate, bench=None,
             precision: str | None = None) -> list:
    harness.ensure_paths()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from benchmarks.chip import faults

    cell = harness.load_cell(workload, bench)
    gate(cell.chips)
    if precision:
        cell.config["sweep"]["matmul_precision"] = precision
    Generator = harness.load_generator(cell.traffic["generator"])
    undo = faults.plant(mode) if mode in faults.FAULTS else None
    out, pool = [], None
    try:
        for s in seeds:
            t0 = time.perf_counter()
            drv = Generator(cell=cell, seed=s, spans=harness.Spans(),
                         rgcn_overrides=LOW_POLICY if mode == "low_policy"
                         else None)
            drv.setup(warm=False, pool=pool)
            pool = getattr(drv, "graphs", None)
            drv.run_window(seconds)
            drv.free()
            checks = {c.name: c.value for c in drv.check()}
            checks.update(getattr(drv, "observed", {}))
            if mode.startswith("reference_"):
                got = drv.reference_gap(mode[len("reference_"):])
                checks = got if isinstance(got, dict) else {"emb_gap": got}
            rec = {"mode": mode, "precision": precision, "seed": s,
                   "checks": checks,
                   "seconds": time.perf_counter() - t0}
            print(json.dumps(rec), flush=True)
            out.append(rec)
    finally:
        if undo:
            undo()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--low-policy-seeds", default="")
    ap.add_argument("--reference-modes", default="",
                    help="e.g. reference_bfloat16")
    ap.add_argument("--precision", default=None,
                    help="the K-sweep's matmul precision in the sound "
                         "readings, in place of the configuration's")
    ap.add_argument("--precision-controls", default="",
                    help="e.g. high: the program with its K-sweep at that "
                         "precision, on the control seeds")
    ap.add_argument("--seconds", type=float, default=5.0)
    a = ap.parse_args(argv)

    def ints(s):
        return [int(x) for x in s.split(",") if x]

    sound = readings(a.workload, ints(a.seeds), a.seconds, "program",
                     precision=a.precision)
    ctrl = readings(a.workload, ints(a.control_seeds), a.seconds,
                    control_mode(a.workload))
    planted = {f: readings(a.workload, ints(a.fault_seeds), a.seconds, f)
               for f in a.faults.split(",") + a.reference_modes.split(",")
               if f}
    for p in a.precision_controls.split(","):
        if p:
            planted[f"precision_{p}"] = readings(
                a.workload, ints(a.control_seeds), a.seconds, "program",
                precision=p)
    planted["low_policy"] = readings(a.workload, ints(a.low_policy_seeds),
                                     a.seconds, "low_policy")
    names = sorted({k for r in sound + ctrl for k in r["checks"]})

    def values(recs, n):
        return [r["checks"][n] for r in recs if n in r["checks"]]

    summary = {n: {"lower": max(values(sound, n), default=None),
                   "control_min": min(values(ctrl, n), default=None),
                   **{f"{f}_min": min(values(rs, n), default=None)
                      for f, rs in planted.items()}}
               for n in names}
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

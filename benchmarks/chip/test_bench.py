"""CPU tests of the on-chip benchmark at a tiny size.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip

The device gate is replaced by one that accepts the CPU; everything else of
a run is driven as on the chip: set-up, window, comparison.  The fault tests
break the timed path underneath and see ``correct`` come out false.
"""

from __future__ import annotations

import copy
import json
import os
import subprocess
import sys

import pytest

from benchmarks.chip import control, faults, harness, run

harness.ensure_paths()


def cpu_gate(chips):
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": jax.device_count()}


def tiny_bench(tmp_path) -> dict:
    """BENCHMARK.json with both configurations cut to a CPU-sized run."""
    bench = harness.bench_file()
    out = copy.deepcopy(bench)
    for c in out["configs"]:
        cfg = harness.load_json(os.path.join(harness.REPO, c["file"]))
        if c["name"] == "paper-suite":
            cfg["programs"] = ["3mm", "backprop", "lud"]
            cfg["sweep"].update(k_max=6, iters=4)
        else:
            cfg["programs"] = ["model:mamba2-780m:decode"]
            cfg["pool"]["graphs"] = 12
            cfg["train"].update(steps=4, batch_size=4, warmup_steps=2,
                                total_steps=4)
        path = tmp_path / f"{c['name']}.json"
        path.write_text(json.dumps(cfg))
        c["file"] = str(path)
    return out


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tiny_bench(tmp_path_factory.mktemp("bench"))


def run_tiny(bench, cell, seed=3, seconds=0.5):
    return run.run_cell(cell, seed, seconds, False, bench=bench, gate=cpu_gate)


@pytest.mark.parametrize("cell", ["suite-plan", "zoo-fit"])
def test_sound_run_is_correct(bench, cell):
    result, checks = run_tiny(bench, cell)
    assert result["correct"], result
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) >= {"setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert list(result)[-1] == "checks"
    assert {c.name for c in checks} == set(result["checks"])


def test_same_seed_same_numbers(bench):
    a, _ = run_tiny(bench, "zoo-fit", seed=11)
    b, _ = run_tiny(bench, "zoo-fit", seed=11)
    assert a["checks"] == b["checks"]


# -- faults planted under the timed path -------------------------------------


@pytest.mark.parametrize("cell,fault", [
    ("zoo-fit", "state_unchanged"),
    ("zoo-fit", "half_batch"),
    ("zoo-fit", "loss_altered"),
    ("suite-plan", "embedding_altered"),
    ("suite-plan", "representative_altered"),
    ("suite-plan", "labels_shuffled"),
])
def test_fault_makes_run_incorrect(bench, cell, fault):
    undo = faults.plant(fault)
    try:
        result, _ = run_tiny(bench, cell)
    finally:
        undo()
    assert result["correct"] is False, result["checks"]


@pytest.mark.parametrize("cell", ["suite-plan", "zoo-fit"])
def test_control_fails_a_number(bench, cell):
    """The control, the reference with its matrix products one step below
    the configuration's precision put in the program's place, fails at
    least one number."""
    recs = control.readings(cell, [5], 0.5, control.control_mode(cell, bench),
                            gate=cpu_gate, bench=bench)
    lim = harness.load_cell(cell, bench).limits["limits"]
    assert any(v > lim[k] for k, v in recs[0]["checks"].items()), recs


def test_reference_sweep_matches_sequential_sweep():
    """On separated clusters of repeated points the float64 reference sweep
    picks the partition and K of the program's sequential sweep."""
    import numpy as np

    from benchmarks.chip import reference
    from repro.core.clustering import select_k_and_cluster

    rng = np.random.default_rng(0)
    u = rng.normal(size=(7, 16)) * 3.0
    u[5] = u[4] + 0.05                    # two distinct points, one cluster
    inv = rng.integers(0, len(u), 300)
    sw = {"k_max": 12, "iters": 20, "sil_cap": 200, "sil_floor": 0.2,
          "tie_tol": 0.02, "tiny_n": 4}
    lab, info = reference.sweep(u, inv, 3, sw)
    got, ginfo = select_k_and_cluster(
        u[inv].astype(np.float32), k_max=12, seed=3, sil_cap=200, iters=20)
    assert info["mode"] == ginfo["mode"] == "silhouette"
    assert info["k"] == ginfo["k"] == 6
    pairs = set(zip(lab.tolist(), np.asarray(got).tolist()))
    assert len(pairs) == info["k"]        # the same partition
    assert abs(info["sil"] - ginfo["sil"]) < 1e-3
    assert reference.silhouette_of(u, inv, got, 3, 200) == pytest.approx(
        info["sil"], abs=1e-9)


# -- the command ---------------------------------------------------------------


def test_no_tpu_no_result():
    """Without a TPU the command exits non-zero and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH_DIR, "run.py"),
         "--workload", "suite-plan", "--seed", str(2**33 + 1), "--seconds",
         "1", "--trace", "0"], cwd=harness.REPO, env=env,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "no TPU" in proc.stderr

"""chip_smoke.py and the failure paths it relies on, without a chip.

- without a TPU the script exits non-zero and prints no result line;
- with the device gate steered by the test, phases A-C run end to end at
  a tiny size on the CPU (Pallas interpreted);
- the plan engine counts every sequential re-run of a failed dispatch;
- the launchers exit non-zero on a hidden fallback or a failed future;
- the compile-cache helper honors ``JAX_COMPILATION_CACHE_DIR``.
"""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _load_script():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod     # dataclass fields resolve through it
    spec.loader.exec_module(mod)
    return mod


def _run_script(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _assert_no_result(proc):
    assert proc.returncode != 0
    lines = proc.stdout.strip().splitlines()
    assert not lines or '"ok": true' not in lines[-1]


def test_script_fails_without_tpu():
    proc = _run_script(REPO, SCRIPT)
    _assert_no_result(proc)
    assert "no TPU" in proc.stderr


def test_script_fails_outside_the_repo(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    proc = _run_script(str(tmp_path), str(tmp_path / "chip_smoke.py"))
    _assert_no_result(proc)


def test_phases_run_at_tiny_size(monkeypatch, tmp_path, capsys):
    """Phases A-C with the gate steered to the CPU: every check passes."""
    smoke = _load_script()
    cpu = {"platform": "cpu", "kind": "cpu", "count": 1}
    monkeypatch.setattr(smoke, "device_gate", lambda min_count: cpu)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    tiny = smoke.Sizes(program="bfs", serve_programs=("3mm", "backprop"),
                       steps=4, batch_size=4, k_max=6, iters=6,
                       cap_instr=48, encode_graphs=16)
    assert smoke.run(1, tiny) == cpu
    out = capsys.readouterr().out
    assert "[check] FAIL" not in out
    for phase in ("A sweep vs sequential", "B builds while warm = 0",
                  "replayed the encoder", "C rgcn_fused encode",
                  "C Pallas sweep vs jnp sweep"):
        assert phase in out


SHARDED_TINY = """
import sys
import jax
sys.path.insert(0, {repo!r})
import chip_smoke as s
s.device_gate = lambda min_count: {{"platform": "cpu", "count": 4}}
s.run(4, s.Sizes(shard_program="3mm", cap_instr=48, batch_size=4,
                 shard_steps=2, k_max=4, iters=4))
"""


def test_sharded_phase_on_virtual_devices(tmp_path):
    """``--chips 4``'s phase on four virtual CPU devices (its own process:
    the device count is fixed before JAX starts)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c",
                           SHARDED_TINY.format(repo=REPO)], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "[check] FAIL" not in proc.stdout
    assert "S plan programs spread over 4 distinct devices" in proc.stdout


def test_fallback_dispatches_counts_forced_failure():
    from repro.sampling.engine import PlanEngine

    rng = np.random.default_rng(0)
    embs = [rng.normal(size=(20 + i, 4)).astype(np.float32) for i in range(3)]
    eng = PlanEngine(k_max=4, iters=4, max_batch=4)

    def fail():
        raise RuntimeError("forced dispatch failure")

    eng.fault_hook = fail
    out = eng.cluster_many(embs, errors="isolate")
    assert eng.stats["fallback_dispatches"] == 3
    assert all(not isinstance(r, Exception) for r in out)
    with pytest.raises(RuntimeError):
        eng.cluster_many(embs, errors="raise")
    eng.fault_hook = None
    eng.reset_stats()
    eng.cluster_many(embs, errors="isolate")
    assert eng.stats["fallback_dispatches"] == 0


def test_sample_exits_nonzero_on_batch_plan_error(monkeypatch, tmp_path):
    from repro.launch import sample
    from repro.sampling.methods import SieveMethod

    def broken(self, items):
        raise RuntimeError("batched planning broke")

    monkeypatch.setattr(SieveMethod, "plan_batch", broken)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    out = tmp_path / "run"
    rc = sample.main(["--method", "sieve", "--programs", "3mm",
                      "--out", str(out)])
    doc = json.loads((out / "results.json").read_text())
    assert doc["batch_plan_errors"] and not doc["failures"]
    assert rc == 1


def test_plan_serve_exits_nonzero_on_failed_futures(monkeypatch, tmp_path):
    from repro.launch import plan_serve
    from repro.sampling.engine import PlanEngine

    def broken(self, requests, errors="raise"):
        raise RuntimeError("engine down")

    monkeypatch.setattr(PlanEngine, "plan_many", broken)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    rc = plan_serve.main(["--requests", "3", "--load", "500", "--no-warmup",
                          "--d", "4", "--k-max", "4", "--iters", "4"])
    assert rc == 1


def test_compile_cache_placed_by_env(monkeypatch, tmp_path):
    import jax

    from repro.compile_cache import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing set


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch):
    import jax

    from repro.compile_cache import DEFAULT_DIR, enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        assert enable_compile_cache() == DEFAULT_DIR
        assert jax.config.jax_compilation_cache_dir == DEFAULT_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert DEFAULT_DIR == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()

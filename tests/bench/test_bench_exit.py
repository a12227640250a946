"""A run without a TPU, or outside a full checkout, fails and prints no
result line."""
import json
import os
import shutil
import subprocess
import sys

import catalog


def run_bench(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "rwkv6-1.6b-3l.iot-s64", "--seed", str(2**31 + 3), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def no_result(stdout: str) -> bool:
    lines = [x for x in stdout.splitlines() if x.strip()]
    if not lines:
        return True
    try:
        return "correct" not in json.loads(lines[-1])
    except ValueError:
        return True


def test_without_a_tpu_the_run_fails_with_no_result():
    p = run_bench(catalog.ROOT, {})
    assert p.returncode != 0
    assert no_result(p.stdout)
    assert "needs a TPU" in p.stderr


def test_with_only_the_benchmark_files_the_run_fails(tmp_path):
    shutil.copy(catalog.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(catalog.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_bench(tmp_path, {})
    assert p.returncode != 0
    assert no_result(p.stdout)

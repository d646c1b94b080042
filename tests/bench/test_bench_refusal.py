"""The command refuses to measure where it cannot: no TPU, or a checkout
that holds only the benchmark's own files."""

import json
import os
import shutil
import subprocess
import sys

from bench import spec


def run(cwd, *extra, env=None):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "merge-w64-uniform",
         "--seed", "1", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, capture_output=True, text=True, timeout=300,
        env=env or dict(os.environ, JAX_PLATFORMS="cpu"))


def no_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return False
        except ValueError:
            continue
    return True


def test_refuses_a_cpu():
    p = run(spec.ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert no_result(p.stdout)


def test_refuses_without_the_program(tmp_path):
    bench = spec.load_benchmark()
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    for p in bench["paths"]:
        shutil.copytree(spec.ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = run(tmp_path)
    assert p.returncode != 0
    assert no_result(p.stdout)

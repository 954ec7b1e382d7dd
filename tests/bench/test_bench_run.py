"""``bench/run.py`` refuses to measure where it cannot: without a TPU it
exits non-zero and prints no result line, and so it does in a directory
that holds only ``BENCHMARK.json`` and the benchmark's own files."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "perm1024.run", "--seed", str(2**33 + 1),
        "--seconds", "1", "--trace", "0"]


def _run(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def _no_result(proc):
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        raise AssertionError(f"result line printed: {line}")


def test_cpu_only_machine_gets_no_result():
    proc = _run(ROOT)
    _no_result(proc)
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_get_no_result(tmp_path):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in spec["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    _no_result(_run(tmp_path))

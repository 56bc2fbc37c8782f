"""A run refuses, with no result line, where there is no TPU."""
import json
import pathlib
import shutil
import subprocess
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]


def run(script, tmp_path):
    return subprocess.run(
        [sys.executable, str(script), "--workload",
         "granite-3-8b-l4.train-s4k", "--seed", str(2**33 + 1),
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(tmp_path),
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache")})


def no_result(out):
    return not any(line.startswith("{") and "correct" in json.loads(line)
                   for line in out.stdout.splitlines())


def test_cpu_is_refused(tmp_path):
    out = run(BENCH / "run.py", tmp_path)
    assert out.returncode != 0 and no_result(out)
    assert "refused: no TPU attached" in out.stderr


def test_benchmark_files_alone_are_refused(tmp_path):
    """A checkout with BENCHMARK.json and the benchmark's own files, but
    not the program, gives no result."""
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    out = run(tmp_path / "perfbench" / "run.py", tmp_path)
    assert out.returncode != 0 and no_result(out)

"""The command refuses to run without a TPU, and without the program."""
import os
import shutil
import subprocess
import sys

from chip_bench import spec

ARGS = ["--workload", "gpt-paper-2L.flan-mix", "--seed", str(2**31 + 9),
        "--seconds", "1", "--trace", "0"]


def _run(cwd, env):
    return subprocess.run([sys.executable, "-m", "chip_bench.run", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(spec.ROOT / "src"))
    r = _run(spec.ROOT, env)
    assert r.returncode == 3, r.stderr
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_refuses_with_only_the_benchmark(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "chip_bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = _run(tmp_path, env)
    assert r.returncode == 2, r.stderr
    assert r.stdout.strip() == ""

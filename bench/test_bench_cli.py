"""`bench/run.py` refuses to run without a TPU, and without the program,
and then prints no result."""
import os
import pathlib
import shutil
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
ARGS = ["--workload", "starcoder2-3b.decode-4k", "--seed", str(2 ** 31 + 7),
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py"] + ARGS, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""

"""``bench/run.py`` refuses to run without a chip or without the program."""
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ARGS = ["--workload", "lenet300-b128-closed", "--seed", "3000000017",
        "--seconds", "1", "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, os.path.join(root, "bench", "run.py"), *ARGS],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_without_a_tpu_it_exits_nonzero_and_names_the_chip():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "no TPU chip found" in p.stderr
    assert p.stdout.strip() == ""


def test_without_the_program_it_exits_nonzero_and_prints_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert "no repro package" in p.stderr
    assert p.stdout.strip() == ""

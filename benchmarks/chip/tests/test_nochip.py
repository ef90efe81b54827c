"""Without a TPU the benchmark fails and prints no result, and so it does
in a directory that holds only BENCHMARK.json and the benchmark's files."""
import os
import shutil
import subprocess
import sys

import run

ARGS = ["--workload", "chr21.se150", "--seed", "3000000019", "--seconds",
        "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "benchmarks/chip/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_chip_no_result():
    p = _run(run.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    """Past the look for a chip, the run needs the program: with only the
    benchmark's files it stops at the first import of it."""
    shutil.copy(run.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(run.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    assert _run(tmp_path).returncode != 0
    code = ("import sys; sys.path.insert(0, 'benchmarks/chip'); import run; "
            "sys.path[:0] = [str(run.ROOT / 'src')]; "
            "print(run.run_cell(run.load_cell('chr21.se150'), 1, 1.0, "
            "False, require_chip=False))")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "No module named 'repro'" in p.stderr

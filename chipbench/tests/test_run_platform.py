"""``run.py`` measures only on a TPU: elsewhere, and without the program
beside it, it exits non-zero and prints no result."""
import os
import shutil
import subprocess
import sys

import harness

RUN = harness.HERE / "run.py"


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, str(script), "--workload", "granite-3-8b-pp2.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cpu_platform_is_refused():
    p = _run(harness.ROOT, RUN)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_alone_is_refused(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, tmp_path / "chipbench" / "run.py")
    assert p.returncode != 0
    assert p.stdout.strip() == ""

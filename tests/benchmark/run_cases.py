"""Run ``cases_*.py`` modules as a pytest session of their own, and fail
with the child's report if any case fails or did not run. Used by
``test_benchmark_cases.py``, which says why the cases are not collected
directly."""

import os
import re
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))


def run_cases(modules, expect_passed: int, marker: str):
    env = {k: v for k, v in os.environ.items() if not k.startswith("PYTEST_")}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "pytest",
         *[os.path.join(HERE, m) for m in modules], "-q", "-m", marker,
         "-p", "no:cacheprovider", "-p", "no:randomly"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    report = out.stdout[-6000:] + out.stderr[-2000:]
    assert out.returncode == 0, report
    passed = re.search(r"(\d+) passed", out.stdout)
    # every case ran: none silently deselected, skipped or lost
    assert passed and int(passed.group(1)) == expect_passed, report

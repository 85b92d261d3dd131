"""Run ``cases_*.py`` modules as a pytest session of their own, and fail
with the child's report unless every case of every module ran and passed.
Used by ``test_benchmark_cases.py``, which says why the cases are not
collected directly."""

import os
import re
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
OUTCOME = re.compile(r"^\S*?(cases_\w+\.py)::\S.* "
                     r"(PASSED|FAILED|ERROR|SKIPPED|XFAIL|XPASS)\b", re.M)


def run_cases(modules):
    """No count is expected: a configuration or a cell added to the
    manifest adds cases. What may not happen is a case that failed, was
    skipped or was deselected, or a module that ran none."""
    env = {k: v for k, v in os.environ.items() if not k.startswith("PYTEST_")}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "pytest",
         *[os.path.join(HERE, m) for m in modules], "-v",
         "-p", "no:cacheprovider", "-p", "no:randomly"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    report = out.stdout[-6000:] + out.stderr[-2000:]
    assert out.returncode == 0, report
    outcomes = OUTCOME.findall(out.stdout)
    assert {o for _, o in outcomes} == {"PASSED"}, report
    assert {m for m, _ in outcomes} == set(modules), report
    assert "deselected" not in out.stdout.splitlines()[-1], report

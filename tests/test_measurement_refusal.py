"""A command that prints a device metric fails where JAX found no TPU.

``chip_smoke.py``'s refusal is held by tests/test_chip_smoke.py; the
benchmark's own case runs under ``-m slow`` (tests/benchmark/). This is
the tier-1 copy of the rule for ``benchmark/run.py``, the one command
whose numbers anyone may quote.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_benchmark_run_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", "gpt2-124m.collective.1chip", "--seed", "0",
         "--seconds", "1", "--trace", "0"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == "", "a result line from a CPU"
    assert "needs 1 tpu device(s)" in out.stderr
    assert "'platform': 'cpu'" in out.stderr

"""The heavier cases of the benchmark's tests, in the slow tier.

``cases_trace_reduce.py``, ``cases_yardstick.py`` and ``cases_rehearsal.py``
(the trace reduction against recorded captures, the yardstick's arithmetic
over every configuration of the manifest, whole cells tiny on the CPU through
``benchmark/run.py``) run here, in a child pytest, under ``-m slow`` — not in
tier-1, where ``test_benchmark_manifest.py`` and ``test_bridge_reader.py``
run; ``cases_rehearsal_ps.py`` (the PS cell with a real fleet) under
``-m ps``. Reason: with them in tier-1, in any
arrangement tried (four files, one file, one test at the end of the
schedule), the existing ``tests/test_training.py`` aborted in 9 of 16 whole
runs of the suite (``Fatal Python error: Aborted``, no message, never in
isolation) and the run then hung; without them, 0 of 14 (PERF.md §7). Run
them directly with
``JAX_PLATFORMS=cpu python -m pytest tests/benchmark/cases_*.py``.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from run_cases import run_cases  # noqa: E402

CASES = ["cases_trace_reduce.py", "cases_yardstick.py", "cases_rehearsal.py"]


@pytest.mark.slow
def test_benchmark_cases():
    run_cases(CASES)


@pytest.mark.ps
def test_ps_cell_fleet_rehearsal():
    run_cases(["cases_rehearsal_ps.py"])

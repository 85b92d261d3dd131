"""The bucketed PS cell's CPU rehearsal, run as ``test_benchmark_cases.py`` runs
the serial cell's (a child pytest, under ``-m ps``: each case starts a real
loopback fleet)."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from run_cases import run_cases  # noqa: E402


@pytest.mark.ps
def test_bucketed_ps_cell_fleet_rehearsal():
    run_cases(["cases_rehearsal_psleg.py"])

"""The PS cell's CPU rehearsal, in a module of its own: it starts a real
loopback fleet (and builds the C core if its stamp is stale), so it runs
under ``-m ps`` through ``test_benchmark_cases.py``."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from cases_rehearsal import REPO, RESULT_KEYS, _rehearse  # noqa: E402


def test_ps_cell_with_a_real_loopback_fleet():
    """The PS cell tiny: scheduler and server children come up, one float32
    gradient tree is pushed per step, both children exit 0 (or the run
    raises), and the readers of the PS leg report from the program's spans
    and the C core's stamps."""
    last, diag = _rehearse("gpt2-124m.ps.1chip", 1, devices=1)
    assert set(last) == RESULT_KEYS | {"breakdown"}
    assert last["correct"] is True and last["failed"] == 0
    m = last["metrics"]
    assert m["ccore.push_bytes_per_step"]["value"] == 4 * diag["n_params"]
    assert "ccore.round_wall_ms" not in m          # retired in PR 26
    assert m["fleet.start_s"]["value"] > 0
    assert "setup.ccore_build_s" in m and diag["rounds"] == diag["steps"]
    # The eight metrics of the PS leg. Seven are on a CPU's line; the
    # eighth, bridge.h2d_ms, is cut at the start of the next program on the
    # device, and a CPU capture has no line of programs: a reader that
    # finds nothing reports nothing. All eight are on the chip's line
    # (PERF.md, PR 26).
    for name in ("bridge.push_pull_ms", "bridge.d2h_ms", "bridge.stage_ms",
                 "bridge.wait_ms", "round.elapsed_ms", "round.push_window_ms",
                 "round.pull_window_ms"):
        assert m[name]["value"] > 0 and m[name]["unit"] == "ms", name
    assert "bridge.h2d_ms" not in m
    assert m["bridge.push_pull_ms"]["value"] >= max(
        m["bridge.stage_ms"]["value"], m["bridge.wait_ms"]["value"])
    # the one idle gap of a PS step is split over the program's spans
    gaps = [name for name, _ in last["breakdown"]["idle_gaps"]]
    assert {"bps.ps.stage", "bps.ps.wait"} <= set(gaps)
    logs = os.listdir(os.path.join(REPO, ".benchmark_out",
                                   "gpt2-124m.ps.1chip", "fleet"))
    assert sorted(logs) == ["scheduler0.log", "server1.log"]

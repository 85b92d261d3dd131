"""The CPU rehearsal of the PS cell whose step overlaps the leg with the
backward pass (``gpt2-124m.ps-bucketed.1chip``), in a module of its own for the
reason ``cases_rehearsal_ps.py`` gives: it starts a real loopback fleet, so it
runs under ``-m ps`` through ``test_psleg_cases.py``."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from cases_rehearsal import END_TO_END, REPO, RESULT_KEYS, _rehearse  # noqa: E402

CELL = "gpt2-124m.ps-bucketed.1chip"
ROUNDBUSY = {"roundbusy." + part for part in (
    "feed_wait_ms", "credit_blocked_ms", "push_thread_ms", "send_blocked_ms",
    "server_ms", "recv_thread_ms", "van_recv_ms")}


def test_end_to_end_line():
    """Untraced: the five end-to-end metrics, one float32 gradient tree
    pushed a step (``correct`` holds it), scheduler and server exit 0."""
    last, diag = _rehearse(CELL, 0, devices=1)
    assert set(last) == RESULT_KEYS
    assert last["correct"] is True and last["failed"] == 0
    assert set(last["metrics"]) == END_TO_END
    assert diag["problems"] == [] and diag["rounds"] == diag["steps"] > 0
    assert diag["agreement"]["max_loss_diff"] < 2e-3


def test_traced_line():
    """Traced: the leg from the program's spans, the round's resources from
    the C core's stamps, the device's idle time named by the program's
    spans. ``psleg.first_push_ms`` is cut at a program's start on the
    device, and a CPU capture has no line of programs: a reader that finds
    nothing reports nothing (it is on the chip's line; PERF.md, PR 52). The
    round as elapsed time is ``round.elapsed_ms``, whose list names this
    cell since PR 66 (``psleg.round_ms``, the same number under this
    reader's name, left the manifest for it); the metrics whose
    ``workloads`` lists name the serial cell alone stay off the line."""
    last, diag = _rehearse(CELL, 1, devices=1)
    assert set(last) == RESULT_KEYS | {"breakdown"}
    assert last["correct"] is True and last["failed"] == 0
    m = last["metrics"]
    assert set(m) == ROUNDBUSY | {
        "step.device_ms", "step.programs_per_step", "device.idle_pct",
        "setup.compile_s", "psleg.leg_ms", "psleg.hidden_ms",
        "psleg.exposed_ms", "round.elapsed_ms"}
    assert m["psleg.leg_ms"]["value"] > 0 and m["round.elapsed_ms"]["value"] > 0
    assert m["psleg.hidden_ms"]["value"] + m["psleg.exposed_ms"]["value"] \
        == pytest.approx(m["psleg.leg_ms"]["value"], abs=1e-6)
    assert all(v["unit"] == "ms" for k, v in m.items()
               if k.startswith(("psleg.", "roundbusy.")))
    gaps = {name for name, _ in last["breakdown"]["idle_gaps"]}
    assert {"bps.ps.stage", "bps.ps.wait"} & gaps
    assert diag["traced_steps"] == 4 and diag["problems"] == []
    out = os.path.join(REPO, ".benchmark_out", CELL)
    assert sorted(os.listdir(os.path.join(out, "fleet"))) == [
        "scheduler0.log", "server1.log"]
    assert os.path.exists(os.path.join(out, "round_summary.json"))

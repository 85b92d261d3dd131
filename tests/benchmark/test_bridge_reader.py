"""The readers of the PS leg (``benchmark/layers/bridge.py``, ``round.py``)
against event lists with hand-worked answers: a made-up one first, then one
cut from a real capture of ``gpt2-124m.ps.1chip`` on the chip. How the
device's idle time is split over these spans is the reduction's business
(``cases_trace_reduce.py``, on the same two lists). No JAX here: the
reductions touch no file and no device."""

import gzip
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
from bench_tiny import REPO  # noqa: E402,F401

from benchmark.layers import bridge, round as round_reader  # noqa: E402
from benchmark.lib import loop  # noqa: E402
from benchmark.lib import trace_reduce as tr  # noqa: E402

DEV0, HOST = "/device:TPU:0", "/host:CPU"


def _step(t0, *, grad_end, d2h_end, stage_end, wait_end, apply_start):
    """One made-up PS step starting at ``t0`` (microseconds): the gradient
    program t0+100..grad_end, the apply program apply_start..+50; caller
    thread ``main``, bridge thread ``bridge``."""
    dev = [(DEV0, "XLA Modules", "jit_grad_step(1)", t0 + 100, grad_end - t0 - 100),
           (DEV0, "XLA Ops", "%fusion.1 = f32[8]{0} fusion(%p)", t0 + 100,
            grad_end - t0 - 100),
           (DEV0, "XLA Modules", "jit_apply_step(2)", apply_start, 50),
           (DEV0, "XLA Ops", "%fusion.2 = f32[8]{0} fusion(%p)", apply_start,
            50)]
    main = [(HOST, "main", loop.STEP_SPAN, t0, 960),
            (HOST, "main", loop.SPANS[1], t0 + 50, 905),        # bench.step
            (HOST, "main", "bps.step.grad", t0 + 50, 40),
            (HOST, "main", "bps.step.ps", t0 + 100, 830),
            (HOST, "main", "bps.step.apply", t0 + 935, 20)]
    thread = [(HOST, "bridge", "bps.ps.push_pull", t0 + 110, 810),
              (HOST, "bridge", "bps.ps.d2h", t0 + 120, d2h_end - t0 - 120),
              (HOST, "bridge", "bps.ps.stage", d2h_end, stage_end - d2h_end),
              (HOST, "bridge", "bps.ps.wait", stage_end,
               wait_end - stage_end),
              (HOST, "bridge", "bps.ps.h2d", wait_end, t0 + 915 - wait_end),
              (HOST, "bridge", "np.asarray(jax.Array)", t0 + 125, 300)]
    return [(p, l, n, s * 1000, d * 1000) for p, l, n, s, d in
            dev + main + thread]


def _made_up():
    """Two steps. Step 1 (from 0): grad program 100..400, d2h 120..500 → 100
    after the program's end; stage 500..520; wait 520..800; h2d span
    800..915 but the apply program starts at 900 → 100. Step 2 (from 1000):
    grad 1100..1400, d2h 1120..1450 → 50; stage 1450..1480; wait
    1480..1800; h2d 1800.., apply at 1890 → 90. Both: push_pull 810, inside
    a bps.step.ps of 830."""
    return (_step(0, grad_end=400, d2h_end=500, stage_end=520, wait_end=800,
                  apply_start=900)
            + _step(1000, grad_end=1400, d2h_end=1450, stage_end=1480,
                    wait_end=1800, apply_start=1890))


def test_the_leg_is_split_where_it_happens():
    first, second = bridge.split_steps(_made_up(), tr.TPU)
    assert first == {"push_pull": 0.81, "d2h": 0.1, "stage": 0.02,
                     "wait": 0.28, "h2d": 0.1, "handoff": pytest.approx(0.02),
                     "children_cover": pytest.approx(795 / 810)}
    assert (second["d2h"], second["stage"], second["wait"], second["h2d"]) \
        == (0.05, 0.03, 0.32, 0.09)
    assert bridge.reduce_spans(_made_up(), tr.TPU) == {
        "bridge.push_pull_ms": 0.81,
        "bridge.d2h_ms": pytest.approx(0.075),     # medians of two
        "bridge.stage_ms": pytest.approx(0.025),
        "bridge.wait_ms": pytest.approx(0.30),
        "bridge.h2d_ms": pytest.approx(0.095)}
    assert set(bridge.METRICS) == set(bridge.reduce_spans(_made_up(), tr.TPU))


def test_a_capture_without_the_spans_reports_nothing():
    """The parent commit's program writes no bps.* span: no metric, no
    error. A step whose h2d has no program after it leaves that part out."""
    bare = [e for e in _made_up() if not e[2].startswith("bps.")]
    assert bridge.split_steps(bare, tr.TPU) == []
    assert bridge.reduce_spans(bare, tr.TPU) == {}
    only_first = [e for e in _made_up() if e[3] < 850_000]   # before apply
    (step,) = bridge.split_steps(only_first, tr.TPU)
    assert "h2d" not in step and step["wait"] == 0.28


def _round(elapsed, push, pull, **more):
    return {"round": 0, "wall_us": 22_000_000, "elapsed_us": elapsed,
            "push_window_us": push, "pull_window_us": pull, **more}


def test_round_reader_takes_the_window_s_rounds_as_elapsed_time():
    """Medians over the rounds completed inside the window (the last
    ``completed_total`` difference of the ring), in ms; a C core from
    before the stamps, or a window longer than the ring, reports nothing."""
    rounds = [_round(9e5, 9e5, 9e5), _round(350_000, 300_000, 310_000),
              _round(360_000, 320_000, 330_000),
              _round(340_000, 310_000, 320_000)]
    run = types.SimpleNamespace(counters={
        "round_summary_before": {"completed_total": 10},
        "round_summary_after": {"completed_total": 13, "rounds": rounds}})
    assert round_reader.read(run) == {
        "round.elapsed_ms": 350.0, "round.push_window_ms": 310.0,
        "round.pull_window_ms": 320.0}
    assert set(round_reader.METRICS) == set(round_reader.read(run))
    old = [{"round": r["round"], "wall_us": r["wall_us"]} for r in rounds]
    run.counters["round_summary_after"]["rounds"] = old
    assert round_reader.read(run) == {}
    run.counters["round_summary_after"]["completed_total"] = 10 + 300
    assert round_reader.read(run) == {}
    assert round_reader.read(types.SimpleNamespace(counters={})) == {}


def test_recorded_ps_capture_with_the_program_s_spans():
    """Steps 3 and 4 of a traced window of gpt2-124m.ps.1chip on the chip
    (my chip run, PR 24; TPU v5 lite), first device and the benchmark's and
    the program's spans, times from the third step span — cut like
    ``benchmark/dump_events.py`` cuts, which keeps no ``bps.*`` span. By
    hand, from the list (ns): step 3's ``bps.ps.d2h`` runs 26,002,958 ..
    188,137,273 and its gradient program ends at 134,165,001 → 53,972,272
    of boundary, 108 ms of waiting for compute; its ``bps.ps.h2d`` starts at
    535,143,640, the apply program at 598,214,251 → 63,070,611. Step 4:
    783,837,236 − 728,277,970 = 55,559,266 and 1,186,877,858 −
    1,127,618,952 = 59,258,906."""
    with gzip.open(os.path.join(
            HERE, "data", "ps-1chip-2steps-spans.events.json.gz"), "rt") as f:
        data = json.load(f)
    events = [tuple(e) for e in data["events"]]
    third, fourth = bridge.split_steps(events, tr.TPU)
    assert third == {
        "push_pull": 557.188067, "d2h": 53.972272, "stage": 228.106458,
        "wait": 118.876569, "h2d": 63.070611,
        "handoff": pytest.approx(8.63543),
        "children_cover": pytest.approx(0.99709, abs=1e-5)}
    assert (fourth["d2h"], fourth["h2d"]) == (55.559266, 59.258906)
    assert bridge.reduce_spans(events, tr.TPU) == {
        "bridge.push_pull_ms": pytest.approx(557.1577415),
        "bridge.d2h_ms": pytest.approx(54.765769),
        "bridge.stage_ms": pytest.approx(228.940498),
        "bridge.wait_ms": pytest.approx(116.4308085),
        "bridge.h2d_ms": pytest.approx(61.1647585)}
    # the four parts against the step: both within 5% of the step span less
    # the device's programs (602.3 and 587.3 ms less 118.8 and 118.7)
    for step, whole in ((third, 602.347003 - 118.770132),
                        (fourth, 587.321293 - 118.694501)):
        parts = step["d2h"] + step["stage"] + step["wait"] + step["h2d"]
        assert 0.95 * whole < parts < whole

"""The reader of how much of the PS leg a step design hides
(``benchmark/layers/psleg.py``) against made-up event lists with hand-worked
answers: a serial step, a step whose pushes lie under the device's programs,
a capture without the spans, the apply program that starts late, and rounds
placed on the capture's clock. No JAX here: the reductions touch no file and
no device."""

import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
from bench_tiny import REPO  # noqa: E402,F401

from benchmark.layers import bridge, psleg  # noqa: E402
from benchmark.lib import trace_reduce as tr  # noqa: E402

DEV0, HOST = "/device:TPU:0", "/host:CPU"


def _ns(events):
    """Made-up events are written in microseconds."""
    return [(p, l, n, s * 1000, d * 1000) for p, l, n, s, d in events]


def _program(name, start, dur):
    return [(DEV0, "XLA Modules", name, start, dur),
            (DEV0, "XLA Ops", "%fusion.1 = f32[8]{0} fusion(%p)", start, dur)]


def _serial_step(t0, *, grad=300, gap=20, stage=30, wait=250, apply_after=40):
    """One serial step from ``t0`` (us): the gradient program t0+10 for
    ``grad``; ``bps.ps.stage`` begins ``gap`` after its end (the first leaf's
    landing) and ``bps.ps.wait`` follows it; the apply program starts
    ``apply_after`` after the last settle, which is after ``bps.step.apply``
    has returned."""
    prog_end = t0 + 10 + grad
    first = prog_end + gap
    settled = first + stage + wait
    return (_program("jit_grad_step(1)", t0 + 10, grad)
            + _program("jit_apply_step(2)", settled + apply_after, 50)
            + [(HOST, "main", "bps.step.grad", t0, 5),
               (HOST, "main", "bps.step.ps", t0 + 6, settled + 12 - t0 - 6),
               (HOST, "main", "bps.step.apply", settled + 14, 4),
               (HOST, "bridge", "bps.ps.push_pull", t0 + 8,
                settled + 10 - t0 - 8),
               (HOST, "bridge", "bps.ps.d2h", t0 + 9, first - t0 - 9),
               (HOST, "bridge", "bps.ps.stage", first, stage),
               (HOST, "bridge", "bps.ps.wait", first + stage, wait),
               (HOST, "bridge", "bps.ps.h2d", settled, 10)])


def test_a_serial_step_hides_nothing():
    """Two serial steps. The leg begins after the gradient program's end and
    the apply program after the last settle: ``hidden_ms`` 0, ``exposed_ms``
    the whole leg, ``first_push_ms`` the program's length plus the landing
    (300 + 20, then 320 + 30). The first step's apply program (at 650) has
    run before the second ``bps.step.grad`` begins (at 1000); the next test
    has one that has not."""
    events = _ns(_serial_step(0) + _serial_step(1000, grad=320, gap=30,
                                                wait=200))
    first, second = psleg.split_steps(events, tr.TPU)
    assert (first["first_push"], first["leg"], first["hidden"],
            first["exposed"], first["enqueues"]) == (0.32, 0.28, 0.0, 0.28, 1)
    assert (second["first_push"], second["leg"], second["hidden"],
            second["exposed"]) == (0.35, 0.23, 0.0, 0.23)
    assert psleg.reduce_spans(events, tr.TPU) == {
        "psleg.first_push_ms": pytest.approx(0.335),
        "psleg.leg_ms": pytest.approx(0.255),
        "psleg.hidden_ms": 0.0,
        "psleg.exposed_ms": pytest.approx(0.255)}
    # the same capture through the host boundary's reader: stage and wait
    # are the leg's two parts
    assert bridge.reduce_spans(events, tr.TPU)["bridge.stage_ms"] == 0.03


def test_the_apply_program_of_the_step_before_is_not_this_step_s_first():
    """The serial step returns before its uploads have landed: the next
    ``bps.step.grad`` begins at 1000, the first step's apply program only
    at 1040 and the second step's gradient program at 1100. The second
    step's first program is the one at 1100."""
    second = _serial_step(1000)
    # move the second gradient program behind the late apply program
    second = [e for e in second if e[2] != "jit_grad_step(1)" and not (
        e[1] == "XLA Ops" and e[3] == 1010)] + _program(
            "jit_grad_step(1)", 1100, 300)
    events = _ns(_serial_step(0, apply_after=430) + second)
    late = [e for e in events if e[2] == "jit_apply_step(2)"][0]
    assert late[3] == 1_040_000
    _, step = psleg.split_steps(events, tr.TPU)
    assert step["first_program"] == 1_100_000
    # the leg did not move: 1330..1610; the program now ends at 1400, 70 us
    # into it
    assert (step["first_push"], step["hidden"]) == (0.23, 0.07)


def _overlapped_step(t0):
    """One step of three gradient programs back to back, 100 us each from
    t0+10, the apply program at t0+600. Enqueue spans (two buckets'
    ``bps.ps.stage`` and two ``bps.tap.push``, to hold both names to one
    rule) from t0+130; ``bps.ps.wait`` ends at t0+500."""
    dev = (_program("jit_grad_b2(1)", t0 + 10, 100)
           + _program("jit_grad_b1(2)", t0 + 112, 100)
           + _program("jit_grad_b0(3)", t0 + 214, 100)
           + _program("jit_apply_step(4)", t0 + 600, 50))
    return dev + [
        (HOST, "main", "bps.step.grad", t0, 8),
        (HOST, "main", "bps.step.ps", t0 + 9, 520),
        (HOST, "main", "bps.step.apply", t0 + 530, 5),
        (HOST, "runtime", "bps.tap.push", t0 + 130, 15),
        (HOST, "bridge", "bps.ps.stage", t0 + 150, 40),
        (HOST, "runtime", "bps.tap.push", t0 + 230, 10),
        (HOST, "bridge", "bps.ps.stage", t0 + 330, 30),
        (HOST, "bridge", "bps.ps.wait", t0 + 360, 140),
        (HOST, "bridge", "bps.ps.h2d", t0 + 500, 20)]


def test_pushes_under_a_program_read_the_intersection_exactly():
    """The leg is 130..500 = 370. Programs cover 112..212 and 214..314 of
    it: 130..212 (82) + 214..314 (100) = 182 hidden, 188 exposed; the first
    enqueue comes 120 after the first program's start; four enqueue spans.
    ``hidden + exposed = leg`` to the nanosecond, in a step and on the
    line."""
    events = _ns(_overlapped_step(0) + _overlapped_step(1000))
    steps = psleg.split_steps(events, tr.TPU)
    assert len(steps) == 2
    for step, t0 in zip(steps, (0, 1_000_000)):
        assert step["leg_ns"] == (t0 + 130_000, t0 + 500_000)
        assert step["step"] == (t0, t0 + 535_000)
        assert step["step_ps"] == (t0 + 9_000, t0 + 529_000)
        assert (step["first_push"], step["leg"], step["hidden"],
                step["exposed"], step["enqueues"]) == (
                    0.12, 0.37, 0.182, 0.188, 4)
    line = psleg.reduce_spans(events, tr.TPU)
    assert line == {"psleg.first_push_ms": 0.12, "psleg.leg_ms": 0.37,
                    "psleg.hidden_ms": 0.182,
                    "psleg.exposed_ms": pytest.approx(0.188, abs=1e-12)}
    assert round(1e6 * (line["psleg.hidden_ms"] + line["psleg.exposed_ms"])) \
        == round(1e6 * line["psleg.leg_ms"])
    assert set(line) | {"psleg.round_ms"} == set(psleg.METRICS)


def test_a_capture_without_the_spans_reports_nothing():
    """The parent commit's bucketed and taps steps write no ``bps.step.*``:
    no metric, no error. Nor does a step whose leg cannot be told (no
    enqueue span, or no ``bps.ps.wait``); and without a line of programs
    only ``first_push_ms`` is left out and nothing is hidden."""
    events = _ns(_overlapped_step(0))
    bare = [e for e in events if not e[2].startswith("bps.step.")]
    assert psleg.split_steps(bare, tr.TPU) == []
    assert psleg.reduce_spans(bare, tr.TPU) == {}
    for gone in (("bps.ps.stage", "bps.tap.push"), ("bps.ps.wait",)):
        assert psleg.reduce_spans(
            [e for e in events if e[2] not in gone], tr.TPU) == {}
    host_only = [e for e in events if e[0] == HOST]
    assert psleg.reduce_spans(host_only, tr.TPU) == {
        "psleg.leg_ms": 0.37, "psleg.hidden_ms": 0.0,
        "psleg.exposed_ms": 0.37}


def _run(tmp_path, events, rounds):
    counters = {} if rounds is None else {
        "round_summary_before": {"completed_total": 2},
        "round_summary_after": {"completed_total": 2 + len(rounds),
                                "rounds": rounds}}
    return types.SimpleNamespace(
        counters=counters, events=events, layout=tr.TPU,
        trace=None if events is None else {"steps": 1},
        out_dir=str(tmp_path))


def test_read_hands_on_the_round_and_leaves_its_rows_beside_the_capture(
        tmp_path):
    """``psleg.round_ms`` is ``round.elapsed_ms``, the median over the
    window's rounds — on an untraced run too, where it is all there is; a
    traced run adds the spans' four and saves the rounds' rows for the
    command; a collective run reports nothing."""
    rounds = [{"round": n, "elapsed_us": us, "push_window_us": us - 5,
               "pull_window_us": us - 3, "start_us": 10_000 + n}
              for n, us in ((2, 900), (3, 210_000), (4, 190_000))]
    assert psleg.read(_run(tmp_path, None, rounds)) == {
        "psleg.round_ms": 190.0}
    assert not os.listdir(tmp_path)
    traced = psleg.read(_run(tmp_path, _ns(_overlapped_step(0)), rounds))
    assert traced == {"psleg.round_ms": 190.0, "psleg.first_push_ms": 0.12,
                      "psleg.leg_ms": 0.37, "psleg.hidden_ms": 0.182,
                      "psleg.exposed_ms": pytest.approx(0.188)}
    assert set(traced) == set(psleg.METRICS)
    with open(tmp_path / psleg.ROUNDS_FILE) as f:
        assert json.load(f)["rounds"] == rounds
    assert psleg.read(_run(tmp_path, None, None)) == {}


def test_rounds_land_in_their_steps_through_the_anchor_alone():
    """Two steps whose ``bps.step.ps`` (at 9 and 1009 us of the capture)
    read the core's clock as 5,000,009 and 5,001,009 us: the one shift is
    −5,000,000 us. Round 7 began at 5,000,131 us on the core's clock and
    took 360 us: 1 us after the first enqueue began (130), done 9 us
    before ``bps.ps.wait`` ended (500); it began 122 us inside
    ``bps.step.ps`` and ended 38 before its end (529). Round 8, at
    5,001,120: 10 us BEFORE the second step's first enqueue — outside."""
    events = _ns(_overlapped_step(0) + _overlapped_step(1000))
    steps = psleg.split_steps(events, tr.TPU)
    anchors = [(9_000, 5_000_009_000), (1_009_000, 5_001_009_000)]
    rounds = [{"round": 7, "start_us": 5_000_131, "elapsed_us": 360},
              {"round": 8, "start_us": 5_001_120, "elapsed_us": 300},
              {"round": 9, "start_us": 5_009_000, "elapsed_us": 300}]
    first, second = psleg.align(rounds, steps, anchors)
    assert first == {"round": 7, "round_start_ns": 131_000,
                     "round_end_ns": 491_000, "start_margin_ms": 0.001,
                     "end_margin_ms": 0.009,
                     "step_ps_start_margin_ms": 0.122,
                     "step_ps_end_margin_ms": 0.038}
    assert (second["round"], second["start_margin_ms"],
            second["end_margin_ms"]) == (8, -0.01, 0.08)
    assert psleg.align(rounds, steps, []) == []

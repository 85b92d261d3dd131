"""The reduction from a capture's event list to numbers, against lists whose
answers are worked out by hand: first small made-up ones, then three cut
from real captures on the chip (``data/*.events.json.gz``, made by
``benchmark/dump_events.py``; PR 22's two and PR 24's with the program's
spans)."""

import gzip
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
from bench_tiny import REPO  # noqa: E402,F401

from test_bridge_reader import _made_up as _made_up_ps_steps  # noqa: E402

from benchmark.layers import bridge  # noqa: E402
from benchmark.lib import loop  # noqa: E402
from benchmark.lib import trace_reduce as tr  # noqa: E402

BETWEEN_CALLS = f"{loop.STEP_SPAN} (between calls)"


def test_interval_arithmetic():
    assert tr.union([(5, 9), (0, 2), (1, 3), (9, 10), (4, 4)]) == [
        [0, 3], [5, 10]]
    assert tr.length([[0, 3], [5, 10]]) == 8
    assert tr.clip([(0, 4), (6, 9), (20, 30)], 2, 8) == [(2, 4), (6, 8)]
    assert tr.subtract([[0, 10], [20, 30]], [[2, 3], [8, 22], [29, 40]]) == [
        [0, 2], [3, 8], [22, 29]]
    assert tr.subtract([[0, 10]], []) == [[0, 10]]
    assert tr.subtract([[0, 10]], [[0, 10]]) == []


def test_op_label_and_collective_names():
    name = ("%fusion.12 = f32[8,1024]{1,0:T(8,128)} fusion(f32[8]{0} "
            "%all-reduce-done.3), kind=kLoop")
    assert tr.op_label(name) == "fusion.12 f32[8,1024]"
    assert tr.op_label(
        "%fusion.7 = (f32[8,1023]{1,0:T(8,128)}, bf16[8]{0:T(8)(2,1)}) "
        "fusion(bf16[8]{0} %x), kind=kOutput") == \
        "fusion.7 (f32[8,1023], bf16[8])"
    assert not tr.is_collective(name)       # it only consumes one
    assert tr.is_collective("%all-reduce-start.1 = (f32[4]{0}) "
                            "all-reduce-start(f32[4]{0} %x)")
    assert tr.is_collective("all-gather.7")
    assert not tr.is_collective("%reduce.5 = f32[] reduce(f32[8] %x)")
    assert len(tr.op_label("x" * 300)) == 100


DEV0, DEV1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"


def _made_up():
    """Two steps on two chips in a window of 1000 us (host spans 0..1000;
    the figures below are microseconds).

    chip 0, XLA Ops: fusion 100..400, all-reduce 400..600, fusion 650..900;
    Async XLA Ops: all-gather-start..done 300..500 (overlaps the first
    fusion for 100 and the all-reduce for 100).
      busy = [100,600] ∪ [650,900] = 750; idle gaps 0..100, 600..650,
      900..1000; collectives = [300,600] = 300; exposed = [400,600] = 200.
    chip 1: one op 0..500 → busy 500. Mean busy 625 of 1000.
    XLA Modules, chip 0: programs 100..600 and 650..900 → 750 over 2 steps.
    """
    ops = [(DEV0, "XLA Ops", "%fusion.1 = f32[8]{0} fusion(%p)", 100, 300),
           (DEV0, "XLA Ops", "%all-reduce.2 = f32[8]{0} all-reduce(%f)",
            400, 200),
           (DEV0, "XLA Ops", "%fusion.1 = f32[8]{0} fusion(%p)", 650, 250),
           (DEV0, "Async XLA Ops", "%all-gather-start.3 = f32[8]{0} "
            "all-gather-start(%x)", 300, 200),
           (DEV0, "XLA Modules", "jit__step(1)", 100, 500),
           (DEV0, "XLA Modules", "jit__step(1)", 650, 250),
           (DEV0, "Steps", "0", 100, 800),
           (DEV1, "XLA Ops", "%fusion.9 = f32[8]{0} fusion(%p)", 0, 500),
           (DEV1, "XLA Modules", "jit__step(1)", 0, 500)]
    host = [(HOST, "main", loop.STEP_SPAN, 0, 480),
            (HOST, "main", loop.SPANS[0], 0, 120),      # place_batch
            (HOST, "main", loop.SPANS[1], 120, 360),    # step
            (HOST, "main", loop.STEP_SPAN, 500, 500),
            (HOST, "main", loop.SPANS[1], 500, 120),
            (HOST, "main", loop.SPANS[2], 660, 340),    # fetch_loss
            (HOST, "other", "unrelated host event", 0, 5000)]
    return [(p, l, n, s * 1000, d * 1000) for p, l, n, s, d in ops + host]


def test_reduction_of_a_made_up_capture():
    r = tr.reduce_events(_made_up(), steps=2, spans=loop.SPANS,
                         step_span=loop.STEP_SPAN)
    ns = 1e-6  # the made-up capture counts in microseconds
    assert r["devices"] == 2 and r["steps"] == 2
    assert r["window_s"] == pytest.approx(1000 * ns)
    assert r["busy_s"] == pytest.approx(625 * ns)
    assert r["idle_share"] == pytest.approx(0.375)
    assert r["programs"] == 2 and r["programs_per_step"] == 1.0
    assert r["program_s_per_step"] == pytest.approx(375 * ns)
    assert r["collective_s_per_step"] == pytest.approx(150 * ns)
    assert r["exposed_collective_s_per_step"] == pytest.approx(100 * ns)
    assert r["device_ops"][0] == ["fusion.1 f32[8]", pytest.approx(550 * ns)]
    # the longest operations are those of the back-to-back line only
    assert [n for n, _ in r["device_ops"]] == [
        "fusion.1 f32[8]", "all-reduce.2 f32[8]"]
    # gaps of chip 0, split at span boundaries: 0..100 in place_batch;
    # 600..650 is 600..620 in the second step's bench.step and 620..650
    # between its calls; 900..1000 in fetch_loss
    assert dict((n, pytest.approx(s)) for n, s in r["idle_gaps"]) == {
        loop.SPANS[0]: 100 * ns, loop.SPANS[2]: 100 * ns,
        loop.SPANS[1]: 20 * ns, BETWEEN_CALLS: 30 * ns}


def test_a_program_belongs_to_the_window_it_ends_in():
    """OLMoE's line of PR 65 read 0.9 | 1.0 programs a step: the first
    traced program's start on the device's clock lay a hair before the step
    span that dispatched it. A program that straddles the window's start is
    the window's, whole; one that ended before it (the warm-up's last) and
    one that starts after it are not."""
    events = [((p, l, n, s - 5000, d + 5000)
               if (l, s) == ("XLA Modules", 100_000) and p == DEV0
               else (p, l, n, s, d)) for p, l, n, s, d in _made_up()]
    events += [(DEV0, "XLA Modules", "jit__step(1)", -400_000, 300_000),
               (DEV0, "XLA Modules", "jit__step(1)", 1_000_000, 100_000)]
    r = tr.reduce_events(events, steps=2, spans=loop.SPANS,
                         step_span=loop.STEP_SPAN)
    assert r["window_s"] == pytest.approx(1000e-6)
    assert r["programs"] == 2 and r["programs_per_step"] == 1.0
    # 95..600 and 650..900, neither clipped
    assert r["program_s_per_step"] == pytest.approx((505 + 250) / 2 * 1e-6)


def test_without_host_spans_the_window_is_the_device_s_own_extent():
    events = [e for e in _made_up() if e[0] != HOST]
    r = tr.reduce_events(events, steps=2, spans=loop.SPANS,
                         step_span=loop.STEP_SPAN)
    assert r["window_s"] == pytest.approx(900e-6)       # 0..900
    assert r["busy_s"] == pytest.approx(625e-6)
    assert [n for n, _ in r["idle_gaps"]] == ["between steps"]


def test_a_capture_without_device_operations_reduces_to_nothing():
    host_only = [e for e in _made_up() if e[0] == HOST]
    assert tr.reduce_events(host_only, steps=2, spans=loop.SPANS,
                            step_span=loop.STEP_SPAN) is None
    assert tr.reduce_events([], steps=1, spans=loop.SPANS,
                            step_span=loop.STEP_SPAN) is None


def test_idle_time_goes_to_the_shortest_span_over_it():
    """Two made-up PS steps (``test_bridge_reader._made_up``) with the
    program's spans among those handed over. Device idle, step 1: 0..100
    and 400..900; step 2: 950..1100 and 1400..1890; then 1940..1960 to the
    last span's end. By the shortest span over each stretch, on whichever
    thread:
      0..50 bench.train, 50..90 bps.step.grad, 90..100 bench.step;
      400..500 d2h, 500..520 stage, 520..800 wait, 800..900 h2d;
      950..955 bps.step.apply (935..955), 955..960 bench.train, 960..1000
      no span,
      1000..1050 bench.train, 1050..1090 grad, 1090..1100 bench.step;
      1400..1450 d2h, 1450..1480 stage, 1480..1800 wait, 1800..1890 h2d;
      1940..1955 bps.step.apply (1935..1955), 1955..1960 bench.train.
    What only bench.train covers reads "(between calls)", what no span
    covers "between steps". The span at each gap's middle would be given
    bps.ps.wait for both long gaps whole."""
    events = _made_up_ps_steps()
    r = tr.reduce_events(events, steps=2, spans=loop.SPANS + bridge.SPANS,
                         step_span=loop.STEP_SPAN)
    got = dict(r["idle_gaps"])
    want_us = {"bps.ps.wait": 280 + 320, "bps.ps.h2d": 100 + 90,
               "bps.ps.d2h": 100 + 50, "bps.ps.stage": 20 + 30,
               "bps.step.grad": 40 + 40, "bps.step.apply": 5 + 15,
               loop.SPANS[1]: 10 + 10,
               BETWEEN_CALLS: 50 + 5 + 50 + 5, "between steps": 40}
    assert got == {k: pytest.approx(v * 1e-6) for k, v in want_us.items()}
    assert next(iter(got)) == "bps.ps.wait"            # longest first
    # all of the idle time, once: window 0..1960 less 300+50+300+50 busy
    assert sum(got.values()) == pytest.approx((1960 - 700) * 1e-6)
    assert sum(got.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    # without the program's spans in the list, the same idle time under
    # the benchmark's own (bench.step runs 50..955 of each step): nothing
    # is lost, only named less finely
    plain = tr.reduce_events(events, steps=2, spans=loop.SPANS,
                             step_span=loop.STEP_SPAN)
    assert dict(plain["idle_gaps"]) == {
        loop.SPANS[1]: pytest.approx((50 + 500 + 5 + 50 + 490 + 15) * 1e-6),
        BETWEEN_CALLS: pytest.approx((50 + 5 + 50 + 5) * 1e-6),
        "between steps": pytest.approx(40e-6)}
    assert sum(t for _, t in plain["idle_gaps"]) == pytest.approx(
        sum(got.values()))


# --------------------------------------------------------------------------
# Recorded lists, cut from real captures on the chip (TPU v5 lite).

def _recorded(name):
    with gzip.open(os.path.join(HERE, "data", name), "rt") as f:
        data = json.load(f)
    return data["steps"], [tuple(e) for e in data["events"]]


def _brute_force_busy(events, plane, lo, hi, res=100):
    """The busy union once more, another way: a raster of ``res`` ns cells."""
    import numpy as np

    grid = np.zeros((hi - lo) // res + 1, bool)
    for p, line, _, start, dur in events:
        if p == plane and line in tr.TPU.op_lines:
            a, b = max(start, lo), min(start + dur, hi)
            if b > a:
                grid[(a - lo) // res:(b - lo) // res] = True
    return int(grid.sum()) * res


def _brute_force_idle(events, spans, lo, hi, res=100):
    """The idle split once more, another way: a raster of ``res`` ns cells
    of the first device, each painted with the span over it — "between
    steps" first, then the step spans, then the others from the longest to
    the shortest, so that the shortest stays on top — and the idle cells
    counted by colour. {name: seconds}."""
    import numpy as np

    n = (hi - lo) // res + 1
    busy, owner = np.zeros(n, bool), np.zeros(n, np.int32)
    host = []
    for p, line, name, start, dur in events:
        if p == DEV0 and line in tr.TPU.op_lines:
            a, b = max(start, lo), min(start + dur, hi)
            if b > a:
                busy[(a - lo) // res:(b - lo) // res] = True
        elif p == HOST and name in (loop.STEP_SPAN, *spans):
            host.append((name != loop.STEP_SPAN, -dur, start, name))
    names = ["between steps"]
    for inner, neg_dur, start, name in sorted(host):
        names.append(name if inner else BETWEEN_CALLS)
        a, b = max(start, lo), min(start - neg_dur, hi)
        if b > a:
            owner[(a - lo) // res:(b - lo) // res] = len(names) - 1
    out = {}
    for k in np.unique(owner[~busy]):
        out[names[k]] = out.get(names[k], 0) + int(
            (~busy & (owner == k)).sum()) * res * 1e-9
    return out


def _agrees_with_the_raster(reduced, events, spans, hi):
    """Every line of ``idle_gaps`` within 20 us of the raster's figure, and
    the raster has no line of 20 us or more that ``idle_gaps`` lacks."""
    brute = _brute_force_idle(events, spans, 0, hi)
    got = dict(reduced["idle_gaps"])
    for name in set(got) | set(brute):
        assert abs(got.get(name, 0) - brute.get(name, 0)) < 2e-5, name


def test_recorded_one_chip_ps_capture():
    """Two PS steps of gpt2-124m.ps.1chip (my chip run, PR 22). By hand,
    from the list: the four programs on `XLA Modules` last 113,468,240 +
    5,314,457 + 113,359,804 + 5,312,150 ns (grad, apply, grad, apply); the
    first step span starts at 0 and the last device op ends at
    1,435,392,745 ns; the device idles while the host sits in bench.step
    (device_get, C core round, device_put)."""
    steps, events = _recorded("ps-1chip-2steps.events.json.gz")
    assert steps == 2
    r = tr.reduce_events(events, steps=steps, spans=loop.SPANS,
                         step_span=loop.STEP_SPAN)
    assert r["devices"] == 1 and r["programs"] == 4
    assert r["programs_per_step"] == 2.0
    assert r["program_s_per_step"] == pytest.approx(0.1187273255, rel=1e-9)
    assert r["window_s"] == pytest.approx(1.435392745, rel=1e-9)
    assert r["busy_s"] == pytest.approx(0.237412135, rel=1e-9)
    assert r["idle_share"] == pytest.approx(0.834601, abs=1e-6)
    brute = _brute_force_busy(events, DEV0, 0, 1_435_392_745)
    assert abs(brute * 1e-9 - r["busy_s"]) < 1e-5       # raster of 100 ns
    assert r["collective_s_per_step"] == 0.0            # one chip: none
    assert r["exposed_collective_s_per_step"] == 0.0
    name, seconds = r["device_ops"][0]                  # the logits fusion
    assert name == "fusion.6 (bf16[8,1023,50257], f32[8,1023,50257])"
    assert seconds == pytest.approx(0.010478396, rel=1e-9)
    assert sum(s for _, s in r["device_ops"]) < r["busy_s"]
    # This capture has none of the program's spans: all but 1.5 ms of the
    # idle time lies under bench.step, the rest under the loss fetch and
    # the batch placement on either side of it. The span at each gap's
    # middle was given 1.197980565 s, bench.step for all of it.
    assert r["idle_gaps"][0] == [loop.SPANS[1],
                                 pytest.approx(1.196495394, rel=1e-9)]
    assert dict(r["idle_gaps"][1:]) == {
        loop.SPANS[2]: pytest.approx(0.0008204, rel=1e-9),
        loop.SPANS[0]: pytest.approx(0.00063386, rel=1e-9),
        BETWEEN_CALLS: pytest.approx(0.000030911, rel=1e-9)}
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(
        1.197980565, rel=1e-9)
    assert sum(s for _, s in r["idle_gaps"]) <= r["window_s"] - r["busy_s"]
    _agrees_with_the_raster(r, events, loop.SPANS, 1_435_392_745)


def test_recorded_four_chip_capture():
    """Two steps of bert-large.collective.4chip, chips 0 and 1 of the four
    (my chip run, PR 22). By hand, from the list: chip 0's two programs
    last 226,615,031 + 226,493,637 ns; its `XLA Ops` line holds four
    synchronous all-reduce of f32[366426940] over {0,1,2,3} — two a step,
    25,765,535 + 25,766,100 + 25,764,463 + 25,764,674 ns — and no other op
    runs beside them, so all of that time is exposed; the window runs from
    the first step span (0) to chip 0's last op (458,228,061 ns); chip 0
    starts 5.28 ms into it, while the host places the first batch."""
    steps, events = _recorded(
        "collective-4chip-2steps-2planes.events.json.gz")
    assert steps == 2
    r = tr.reduce_events(events, steps=steps, spans=loop.SPANS,
                         step_span=loop.STEP_SPAN)
    assert r["devices"] == 2 and r["programs"] == 2
    assert r["programs_per_step"] == 1.0
    assert r["program_s_per_step"] == pytest.approx(0.226554334, rel=1e-9)
    assert r["window_s"] == pytest.approx(0.458228061, rel=1e-9)
    assert r["collective_s_per_step"] == pytest.approx(0.051530386, rel=1e-9)
    assert r["exposed_collective_s_per_step"] == r["collective_s_per_step"]
    brute = [_brute_force_busy(events, p, 0, 458_228_061)
             for p in (DEV0, DEV1)]
    assert r["busy_s"] == pytest.approx(0.4528630325, rel=1e-9)
    assert abs(sum(brute) / 2 * 1e-9 - r["busy_s"]) < 5e-5   # raster, 100 ns
    assert r["idle_share"] == pytest.approx(0.0117082, abs=1e-6)
    assert r["device_ops"][:2] == [
        ["all-reduce f32[366426940]", pytest.approx(0.051530774, rel=1e-9)],
        ["all-reduce.1 f32[366426940]", pytest.approx(0.051529998, rel=1e-9)]]
    # the gap at the start, 5,281,442 ns, which the span at its middle
    # gave to place_batch whole: the host places the first batch for
    # 2,861,500 of it and is in bench.step, dispatching, for 2,408,202
    assert r["idle_gaps"][:2] == [
        [loop.SPANS[0], pytest.approx(0.0028615, rel=1e-9)],
        [loop.SPANS[1], pytest.approx(0.002408202, rel=1e-9)]]
    assert dict(r["idle_gaps"])[BETWEEN_CALLS] == pytest.approx(
        0.00001174, rel=1e-9)
    assert (sum(s for n, s in r["idle_gaps"] if n != "between steps")
            == pytest.approx(0.005281442, rel=1e-9))
    _agrees_with_the_raster(r, events, loop.SPANS, 458_228_061)


def test_recorded_ps_capture_idle_split_over_the_program_s_spans():
    """Steps 3 and 4 of a traced window of gpt2-124m.ps.1chip (my chip run,
    PR 24; the list of ``test_bridge_reader.py``, which works its spans out
    by hand). Under the benchmark's spans alone the device's idle time is
    one line, bench.step: 0.941647857 s of 0.949473215. With the program's
    spans handed over as ``lib/cell.py`` hands them, the same time to the
    nanosecond, split. The device idles under all of every stage and wait
    span, so those two lines are the spans' own lengths; d2h is cut at the
    gradient programs' ends (53,972,272 + 55,559,266 ns, and the 1 us or so
    by which a program's last operation ends before its `XLA Modules`
    event); h2d is the two spans themselves (46,451,655 + 48,241,516), the
    rest of the way to the apply program lying under bps.step.ps and
    bps.step.apply."""
    steps, events = _recorded("ps-1chip-2steps-spans.events.json.gz")
    plain = tr.reduce_events(events, steps=steps, spans=loop.SPANS,
                             step_span=loop.STEP_SPAN)
    assert plain["idle_gaps"][0] == [loop.SPANS[1],
                                     pytest.approx(0.941647857, rel=1e-9)]
    r = tr.reduce_events(events, steps=steps,
                         spans=loop.SPANS + bridge.SPANS,
                         step_span=loop.STEP_SPAN, top=20)
    idle = dict(r["idle_gaps"])
    assert list(idle)[:4] == ["bps.ps.stage", "bps.ps.wait", "bps.ps.d2h",
                              "bps.ps.h2d"]
    third, fourth = bridge.split_steps(events, tr.TPU)
    assert idle["bps.ps.stage"] == pytest.approx(
        (third["stage"] + fourth["stage"]) * 1e-3, rel=1e-9)
    assert idle["bps.ps.stage"] == pytest.approx(0.457880996, rel=1e-9)
    assert idle["bps.ps.wait"] == pytest.approx(
        (third["wait"] + fourth["wait"]) * 1e-3, rel=1e-9)
    assert idle["bps.ps.wait"] == pytest.approx(0.232861617, rel=1e-9)
    assert idle["bps.ps.d2h"] == pytest.approx(0.109531538, abs=5e-6)
    assert idle["bps.ps.h2d"] == pytest.approx(0.094693171, rel=1e-9)
    assert sum(idle.values()) == pytest.approx(
        sum(s for _, s in plain["idle_gaps"]), rel=1e-12)
    assert sum(idle.values()) == pytest.approx(
        r["window_s"] - r["busy_s"], abs=1e-6)      # less the gaps < 1 us
    assert max(idle.values()) < 0.8 * sum(idle.values())
    # the result line keeps ten lines; the eleventh here is 3 us long
    ten = tr.reduce_events(events, steps=steps,
                           spans=loop.SPANS + bridge.SPANS,
                           step_span=loop.STEP_SPAN)["idle_gaps"]
    assert len(ten) == 10 and ten == r["idle_gaps"][:10]
    _agrees_with_the_raster(r, events, loop.SPANS + bridge.SPANS,
                            1_190_291_890)


def test_dump_events_keeps_the_spans_the_readers_name(tmp_path):
    """``benchmark/dump_events.py`` cuts a recorded list out of a capture
    (or out of a list): the benchmark's spans and those of every reader in
    ``benchmark/layers/``, so a list cut today can be reduced as above."""
    out = tmp_path / "cut.events.json.gz"
    done = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "dump_events.py"),
         os.path.join(HERE, "data", "ps-1chip-2steps-spans.events.json.gz"),
         str(out)], env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr
    with gzip.open(out, "rt") as f:
        kept = {e[2] for e in json.load(f)["events"] if e[0] == HOST}
    assert kept == {loop.STEP_SPAN, *loop.SPANS, *bridge.SPANS}

"""The reduction from a capture's event list to numbers, against lists whose
answers are worked out by hand: first a small made-up one, then two cut from
this PR's real captures on the chip (``data/*.events.json.gz``, made by
``benchmark/dump_events.py``)."""

import gzip
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
from bench_tiny import REPO  # noqa: E402,F401

from benchmark.lib import loop  # noqa: E402
from benchmark.lib import trace_reduce as tr  # noqa: E402


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
    # gaps of chip 0 by the host span at their middle: 0..100 in
    # place_batch, 600..650 between the second step's calls, 900..1000 in
    # fetch_loss
    assert dict((n, pytest.approx(s)) for n, s in r["idle_gaps"]) == {
        loop.SPANS[0]: 100 * ns, loop.SPANS[2]: 100 * ns,
        f"{loop.STEP_SPAN} (between calls)": 50 * ns}


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


# --------------------------------------------------------------------------
# Recorded lists, cut from this PR's captures on the chip (TPU v5 lite).

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
    assert r["idle_gaps"][0][0] == loop.SPANS[1]        # bench.step
    assert r["idle_gaps"][0][1] == pytest.approx(1.19798, abs=1e-5)
    assert sum(s for _, s in r["idle_gaps"]) <= r["window_s"] - r["busy_s"]


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
    assert r["idle_gaps"][0] == [loop.SPANS[0],
                                 pytest.approx(0.005281442, rel=1e-9)]

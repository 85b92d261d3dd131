"""The reader of the round's resources (``benchmark/layers/roundbusy.py``)
against round rows with hand-worked answers: made-up ones first, then the
rows and the ``bps.ps.push_pull`` spans recorded from a traced run of
``gpt2-124m.ps.1chip`` on the chip (PR 37). No JAX here: the reader touches
no file and no device."""

import gzip
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
from bench_tiny import REPO  # noqa: E402,F401

from benchmark.layers import bridge, roundbusy  # noqa: E402
from benchmark.layers import round as round_reader  # noqa: E402

DATA = os.path.join(HERE, "data")


def _row(n, **fields):
    row = {"round": n, "elapsed_us": 1000, "push_window_us": 900,
           "feed_wait_us": 0}
    for union, total in roundbusy.STAGES.values():
        row[union] = 0
        if total:
            row[total] = 0
    return {**row, **fields}


def _run(rows, completed_before):
    return types.SimpleNamespace(counters={
        "round_summary_before": {"completed_total": completed_before},
        "round_summary_after": {
            "completed_total": completed_before + len(rows), "rounds": rows}})


def test_made_up_medians():
    """Three rounds in the window behind two from before it: the median of
    each field over the three, in milliseconds; ``server_ms`` is the union."""
    old = [_row(n, credit_blocked_us=999) for n in (0, 1)]
    new = [_row(2, feed_wait_us=10, credit_blocked_us=700, push_thread_us=300,
                send_blocked_us=250, server_span_us=600, server_us=1800,
                recv_thread_us=400, van_recv_us=310),
           _row(3, feed_wait_us=0, credit_blocked_us=900, push_thread_us=100,
                send_blocked_us=50, server_span_us=500, server_us=900,
                recv_thread_us=450, van_recv_us=330),
           _row(4, feed_wait_us=30, credit_blocked_us=800, push_thread_us=200,
                send_blocked_us=150, server_span_us=700, server_us=2100,
                recv_thread_us=350, van_recv_us=320)]
    run = _run(old + new, 2)
    run.counters["round_summary_after"]["completed_total"] = 5
    assert roundbusy.read(run) == {
        "roundbusy.feed_wait_ms": 0.010,
        "roundbusy.credit_blocked_ms": 0.800,
        "roundbusy.push_thread_ms": 0.200,
        "roundbusy.send_blocked_ms": 0.150,
        "roundbusy.server_ms": 0.600,
        "roundbusy.recv_thread_ms": 0.400,
        "roundbusy.van_recv_ms": 0.320}
    assert set(roundbusy.METRICS) == set(roundbusy.FIELDS)
    assert roundbusy.LAYER == round_reader.LAYER == "C core"


def test_a_window_longer_than_the_ring_reports_nothing():
    """300 rounds completed, 256 kept: no medians of a part."""
    rows = [_row(n, credit_blocked_us=5) for n in range(256)]
    run = _run(rows, 0)
    run.counters["round_summary_after"]["completed_total"] = 300
    assert roundbusy.read(run) == {}
    assert roundbusy.read(types.SimpleNamespace(counters={})) == {}


def test_a_core_from_before_the_fields_reports_nothing():
    rows = [{"round": 0, "elapsed_us": 1000, "push_window_us": 900}]
    assert roundbusy.read(_run(rows, 0)) == {}
    assert roundbusy.table(rows[0]) == {"elapsed_ms": 1.0}


def test_table_sum_union_depth_share():
    """One row by hand: 4 partitions of 600 us in flight over 800 us of a
    1000 us round."""
    row = _row(7, push_us=2400, push_span_us=800, credit_blocked_us=750,
               feed_wait_us=100)
    t = roundbusy.table(row)
    assert t["elapsed_ms"] == 1.0
    assert t["push"] == {"union_ms": 0.8, "share": 0.8, "sum_ms": 2.4,
                         "depth": 3.0}
    assert t["credit_blocked"] == {"union_ms": 0.75, "share": 0.75}
    assert t["feed_wait"] == {"union_ms": 0.1, "share": 0.1}
    assert t["push_wire_empty_ms"] == pytest.approx(0.1)
    assert t["comp"]["depth"] is None
    assert roundbusy.violations([row]) == []
    bad = _row(8, server_span_us=900, server_us=800, push_span_us=850,
               push_us=850)
    assert roundbusy.violations([bad]) == [
        (8, "server_us", 800), (8, "server_span_us", 900)]


def test_align_places_a_round_inside_its_span():
    """A span that starts at 5,000,000 ns of the capture with ``mono_ns``
    2,000,000,000: a round whose first enqueue the core stamped at
    2,000,300 us began 300 us into the span."""
    rounds = [{"round": 11, "start_us": 1_990_000, "elapsed_us": 500},
              {"round": 12, "start_us": 2_000_300, "elapsed_us": 600},
              {"round": 13, "start_us": 2_002_000, "elapsed_us": 700}]
    spans = [(5_000_000, 1_000_000, 2_000_000_000),
             (9_000_000, 400_000, 2_002_500_000)]     # no round starts in it
    (got,) = roundbusy.align(rounds, spans)
    assert got == {"round": 12, "round_start_ns": 5_300_000,
                   "round_end_ns": 5_900_000, "start_margin_ms": 0.3,
                   "end_margin_ms": 0.1}


# --------------------------------------------------------------------------
# Recorded on the chip: the counters of a traced run of gpt2-124m.ps.1chip
# (the window's round rows, the completed totals around it, the ``mono_ns``
# of the capture's ``bps.ps.push_pull`` spans) and the events of its first
# two traced steps, cut by ``benchmark/dump_events.py``.

RECORDED = os.path.join(DATA, "ps-1chip-roundbusy.counters.json.gz")
RECORDED_EVENTS = os.path.join(DATA, "ps-1chip-roundbusy-2steps.events.json.gz")


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def recorded_spans(recorded):
    """``(start_ns, duration_ns, mono_ns)`` of the cut's two push_pull
    spans: the events give the first two (times from the first step span),
    the counters file the stat the event list has no room for."""
    with gzip.open(RECORDED_EVENTS, "rt") as f:
        events = json.load(f)["events"]
    found = sorted((s, d) for _, _, name, s, d in events
                   if name == bridge.PUSH_PULL)
    assert len(found) == 2
    return [(s, d, mono) for (s, d), mono in zip(
        found, recorded["push_pull_mono_ns"])]


def test_recorded_medians(recorded):
    """65 rounds completed, 2 of them before the window: the reader's seven
    figures are the medians of the last 63 rows — the 32nd of each field
    sorted, read off by hand — and every row holds to the invariants."""
    run = types.SimpleNamespace(counters=recorded)
    rows = roundbusy.window_rounds(recorded)
    assert [r["round"] for r in rows] == list(range(2, 65))
    assert roundbusy.read(run) == {
        "roundbusy.feed_wait_ms": 0.371,
        "roundbusy.credit_blocked_ms": 153.225,
        "roundbusy.push_thread_ms": 200.928,
        "roundbusy.send_blocked_ms": 204.975,
        "roundbusy.server_ms": 113.249,
        "roundbusy.recv_thread_ms": 169.498,
        "roundbusy.van_recv_ms": 127.83}
    assert roundbusy.violations(rows) == []
    # round.py reads the same window
    assert round_reader.read(run)["round.elapsed_ms"] == 363.701
    # the push thread is in writev or waits for credit, one after the other
    for r in rows:
        assert r["push_thread_us"] + r["credit_blocked_us"] <= r["elapsed_us"]


def test_recorded_rounds_lie_inside_their_spans(recorded, recorded_spans):
    """Through ``mono_ns`` alone, each of the two traced steps' rounds lies
    inside that step's ``bps.ps.push_pull``."""
    rows = recorded["round_summary_after"]["rounds"]
    placed = roundbusy.align(rows, recorded_spans)
    assert [got["round"] for got in placed] == [3, 4]    # 3 warm-up steps
    for got, (start, dur, _) in zip(placed, recorded_spans):
        assert start <= got["round_start_ns"] < got["round_end_ns"] <= (
            start + dur)
        assert got["start_margin_ms"] > 0 and got["end_margin_ms"] > 0

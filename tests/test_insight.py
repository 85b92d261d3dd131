"""Per-round introspection unit tests (ISSUE 7).

Fast tier: the C-core round ring (wraparound, drop counters, finalize
rules) driven through the real bps_round_track FFI path; heartbeat
summary wire-format version interop through bps_round_ingest; and the
insight classification engine's state boundaries on synthetic
summaries — every fleet state reachable.
"""

import struct

import pytest

from byteps_tpu.monitor import insight

# Wire layout mirrors csrc/roundstats.h (packed).
_HDR = struct.Struct("<HHiiiqq")
_REC = struct.Struct("<ii7q4i")
_MAGIC = 0xB57A
_VERSION = 1


def _pack_rec(round_no, parts=4, queue=10, comp=5, push=100, sum_us=40,
              pull=50, dec=5, wire_bytes=4096, wire_msgs=8, fused=0,
              retries=0, parked=0):
    return _REC.pack(round_no, parts, queue, comp, push, sum_us, pull,
                     dec, wire_bytes, wire_msgs, fused, retries, parked)


def _pack_summary(node_id, recs, role=2, magic=_MAGIC, version=_VERSION,
                  completed=None, dropped=0):
    hdr = _HDR.pack(magic, version, node_id, role, len(recs),
                    completed if completed is not None else len(recs),
                    dropped)
    return hdr + b"".join(recs)


# --- C ring via FFI (no topology needed) -----------------------------------

def _drive_round(ffi, r, parts=2, push=100, sum_us=40, pull=50,
                 retries=0):
    for _ in range(parts):
        ffi.round_track("enq", r)
    for _ in range(parts):
        ffi.round_track("queue", r, 10)
        ffi.round_track("frame", r)
        ffi.round_track("push", r, push, 1024)
        ffi.round_track("sum", r, sum_us)
        ffi.round_track("frame", r)
        ffi.round_track("pull", r, pull, 1024)
    for _ in range(retries):
        ffi.round_track("retry", r)
    for _ in range(parts):
        ffi.round_track("done", r)


def test_round_ring_accumulates_and_finalizes():
    """A balanced round finalizes once a NEWER round starts (mid-step
    completion of one tensor must not split the round), and the record
    carries the per-stage sums + derived wire_ack."""
    from byteps_tpu.core import ffi

    base = ffi.round_summary()["completed_total"]
    start = 1_000_000  # round-number namespace away from other tests
    _drive_round(ffi, start, parts=3, push=200, sum_us=80)
    s = ffi.round_summary()
    assert all(r["round"] != start for r in s["rounds"]), \
        "round must stay open until a later round starts"
    _drive_round(ffi, start + 1)
    s = ffi.round_summary()
    assert s["completed_total"] >= base + 1
    rec = s["last"]
    assert rec["round"] == start
    assert rec["parts"] == 3
    assert rec["push_us"] == 3 * 200
    assert rec["sum_us"] == 3 * 80
    assert rec["wire_ack_us"] == 3 * (200 - 80)
    assert rec["wire_bytes"] == 3 * 2048
    assert rec["wire_msgs"] == 6
    assert rec["queue_us"] == 30


def test_round_ring_wraparound_and_drop_counter():
    """Drop-oldest semantics: driving more rounds than the ring holds
    keeps the newest records and counts the overwritten ones."""
    from byteps_tpu.core import ffi

    s0 = ffi.round_summary()
    cap = s0["ring_capacity"]
    base_done = s0["completed_total"]
    base_dropped = s0["dropped"]
    n = cap + 40
    start = 2_000_000
    for r in range(start, start + n + 1):
        _drive_round(ffi, r, parts=1)
    s = ffi.round_summary()
    # >= : leftover balanced rounds from earlier tests may finalize too
    # (the singleton is process-wide).
    assert base_done + n <= s["completed_total"] <= base_done + n + 8
    assert s["dropped"] >= base_dropped + 40 - 1
    assert len(s["rounds"]) == cap
    # Newest records survive, oldest rotated out.
    rounds = [r["round"] for r in s["rounds"]]
    assert rounds == sorted(rounds)
    assert rounds[-1] == start + n - 1
    assert rounds[0] >= start + n - cap


def test_round_open_table_is_bounded():
    """Rounds that never balance (failed handles) are force-finalized
    once the open table overflows — the ring keeps moving."""
    from byteps_tpu.core import ffi

    base = ffi.round_summary()["completed_total"]
    start = 3_000_000
    for r in range(start, start + 20):
        ffi.round_track("enq", r)
        ffi.round_track("push", r, 10, 1)
        # never done: the ledger stays unbalanced
    s = ffi.round_summary()
    assert s["completed_total"] > base, \
        "open table must force-finalize wedged rounds"


def test_ingest_version_interop():
    """Only the known magic+version is accepted; short frames and
    foreign generations are ignored (mixed-fleet heartbeats interop)."""
    from byteps_tpu.core import ffi

    good = _pack_summary(41, [_pack_rec(7)])
    assert ffi.round_ingest(good)
    assert not ffi.round_ingest(_pack_summary(41, [_pack_rec(8)],
                                              magic=0x1234))
    assert not ffi.round_ingest(_pack_summary(41, [_pack_rec(8)],
                                              version=_VERSION + 1))
    assert not ffi.round_ingest(good[:20])  # short frame
    # count larger than the payload actually carries
    bad_count = _HDR.pack(_MAGIC, _VERSION, 41, 2, 5, 5, 0) + _pack_rec(9)
    assert not ffi.round_ingest(bad_count)
    s = ffi.round_summary()
    assert "41" in s["fleet"]
    assert s["fleet"]["41"]["last"]["round"] == 7, \
        "rejected payloads must not have touched the fleet table"


def test_ingest_builds_fleet_table_and_ewma():
    from byteps_tpu.core import ffi

    node = 55
    walls = []
    for r in range(5):
        rec = _pack_rec(100 + r, push=1000 * (r + 1), pull=0, queue=0,
                        comp=0, dec=0, sum_us=0)
        walls.append(1000.0 * (r + 1))
        assert ffi.round_ingest(_pack_summary(node, [rec]))
    s = ffi.round_summary()
    st = s["fleet"][str(node)]
    assert st["updates"] == 5
    assert st["last"]["round"] == 104
    # EWMA with alpha 0.2, seeded by the first sample.
    ewma = walls[0]
    for w in walls[1:]:
        ewma = 0.8 * ewma + 0.2 * w
    assert st["ewma_wall_us"] == pytest.approx(ewma, rel=1e-3)
    for r in range(5):
        assert str(node) in s["fleet_rounds"][str(100 + r)]


# --- the round as elapsed time (RoundSpan stamps beside the sums) ----------

def _finalized(ffi, round_no):
    """Drive a later round so ``round_no`` finalizes; return its record."""
    _drive_round(ffi, round_no + 1, parts=1)
    (rec,) = [r for r in ffi.round_summary()["rounds"]
              if r["round"] == round_no]
    return rec


def test_round_elapsed_time_and_windows():
    """Enqueue, push, pull, done with sleeps between: ``elapsed_us`` is
    first enqueue to last done on the core's clock, a window runs from
    the earliest issue (completion - us) to the latest completion, and
    ``wall_us`` is still the plain sum of the stages."""
    import time

    from byteps_tpu.core import ffi

    r = 4_000_000
    t0 = time.monotonic_ns() // 1000
    ffi.round_track("enq", r)
    ffi.round_track("enq", r)
    time.sleep(0.02)
    ffi.round_track("push", r, 15_000, 100)   # issued 15 ms ago: t0 + ~5 ms
    time.sleep(0.03)
    ffi.round_track("push", r, 20_000, 100)   # ends the window at ~50 ms
    ffi.round_track("pull", r, 10_000, 100)   # issued at ~40 ms
    time.sleep(0.02)
    ffi.round_track("pull", r, 5_000, 100)    # ends at ~70 ms
    ffi.round_track("done", r)
    time.sleep(0.01)
    ffi.round_track("done", r)                # ~80 ms
    t1 = time.monotonic_ns() // 1000
    rec = _finalized(ffi, r)
    slack = 15_000   # sleeps overshoot, never undershoot
    assert t0 <= rec["start_us"] <= t0 + slack
    assert 80_000 <= rec["elapsed_us"] <= t1 - t0
    assert 5_000 <= rec["push_offset_us"] <= 5_000 + slack
    assert 45_000 <= rec["push_window_us"] <= 45_000 + slack
    assert 40_000 <= rec["pull_offset_us"] <= 40_000 + slack
    assert 30_000 <= rec["pull_window_us"] <= 30_000 + slack
    # the windows overlap here: the first pull went out before the last ack
    assert rec["pull_offset_us"] < rec["push_offset_us"] + rec[
        "push_window_us"]
    assert rec["push_us"] == 35_000 and rec["pull_us"] == 15_000
    assert rec["wall_us"] == 50_000           # the sums, as before


def test_round_elapsed_time_is_not_partition_time():
    """200 partitions in flight at once, 50 ms each: ``wall_us`` adds them
    up (partition-time, 10 s), ``elapsed_us`` says how long it took."""
    from byteps_tpu.core import ffi

    r, parts = 4_100_000, 200
    for _ in range(parts):
        ffi.round_track("enq", r)
    for _ in range(parts):
        ffi.round_track("push", r, 50_000, 1)
    for _ in range(parts):
        ffi.round_track("done", r)
    rec = _finalized(ffi, r)
    assert rec["wall_us"] == parts * 50_000
    assert rec["elapsed_us"] < 50_000                  # microseconds, really
    assert 50_000 <= rec["push_window_us"] < 100_000   # one push's length
    assert rec["pull_window_us"] == rec["pull_offset_us"] == 0   # no pull


def test_round_stamps_stay_off_the_heartbeat_wire():
    """The stamps live beside RoundRec, not in it: the packed wire element
    keeps its 80 bytes (this file's ``_REC``, ingested above), and fleet
    records, which came over the wire, carry no elapsed-time field."""
    from byteps_tpu.core import ffi

    assert _REC.size == 80 and _HDR.size == 32
    assert ffi.round_ingest(_pack_summary(47, [_pack_rec(11)]))
    fleet_rec = ffi.round_summary()["fleet"]["47"]["last"]
    assert fleet_rec["round"] == 11 and "wall_us" in fleet_rec
    assert not {"start_us", "elapsed_us", "push_window_us",
                "pull_window_us"} & set(fleet_rec)


# --- the round's stages and resources as elapsed time (RoundBusy) -----------

# an arbitrary instant on the core's clock; every event below is placed by hand
_T = 7_000_000_000
_SPAN_OF = {"queue": "queue_span_us", "comp": "comp_span_us",
            "push": "push_span_us", "sum": "sum_span_us",
            "pull": "pull_span_us", "dec": "dec_span_us"}
_RESOURCE_OF = {"credit": ("credit_blocked_us", None),
                "push_thread": ("push_thread_us", "push_thread_sum_us"),
                "send_blocked": ("send_blocked_us", "send_blocked_sum_us"),
                "server": ("server_span_us", "server_us"),
                "recv_thread": ("recv_thread_us", "recv_thread_sum_us"),
                "van_recv": ("van_recv_us", None)}
_SPANS = tuple(_SPAN_OF.values()) + tuple(
    union for union, _ in _RESOURCE_OF.values()) + ("feed_wait_us",)


def _interval(ffi, stage, r, start, end):
    """One duration of ``stage``: [start, end] from ``_T``."""
    ffi.round_track(stage, r, end - start, 0, _T + end)


@pytest.mark.parametrize("stage", sorted(_SPAN_OF))
def test_stage_span_is_the_union_of_its_intervals(stage):
    """Two overlapping intervals and a disjoint one: the sum adds the three
    lengths, the span is the length of their union, worked by hand."""
    from byteps_tpu.core import ffi

    r = 5_000_000 + 10 * sorted(_SPAN_OF).index(stage)
    ffi.round_track("enq", r, 0, 0, _T)
    _interval(ffi, stage, r, 100, 600)
    _interval(ffi, stage, r, 400, 900)      # overlaps the first: 100..900
    _interval(ffi, stage, r, 2000, 2300)    # apart
    ffi.round_track("done", r, 0, 0, _T + 3000)
    rec = _finalized(ffi, r)
    assert rec["start_us"] == _T and rec["elapsed_us"] == 3000
    assert rec[stage + "_us"] == 500 + 500 + 300
    assert rec[_SPAN_OF[stage]] == 800 + 300
    assert rec[_SPAN_OF[stage]] <= rec[stage + "_us"]
    for other in _SPANS:
        assert 0 <= rec[other] <= rec["elapsed_us"]
        if other != _SPAN_OF[stage]:
            assert rec[other] == 0
    if stage == "push":
        # the window runs over the gap, the span does not: 1100 us of the
        # 2200 had no push on the wire
        assert rec["push_window_us"] == 2300 - 100
        assert rec["push_window_us"] - rec["push_span_us"] == 1100


@pytest.mark.parametrize("stage", sorted(_RESOURCE_OF))
def test_resource_busy_time_sum_and_union(stage):
    """A resource's stamps: sum and union as for a stage; what lies outside
    the round's ends does not count; and a stamp opens no round."""
    from byteps_tpu.core import ffi

    r = 5_100_000 + 10 * sorted(_RESOURCE_OF).index(stage)
    union, total = _RESOURCE_OF[stage]
    _interval(ffi, stage, r, 0, 50)     # before any enqueue: dropped
    ffi.round_track("enq", r, 0, 0, _T + 100)
    _interval(ffi, stage, r, 0, 300)        # clipped to 100..300
    _interval(ffi, stage, r, 200, 500)      # overlaps: 100..500
    _interval(ffi, stage, r, 700, 800)
    ffi.round_track("done", r, 0, 0, _T + 1000)
    _interval(ffi, stage, r, 950, 1200)     # clipped to 950..1000
    rec = _finalized(ffi, r)
    assert rec["elapsed_us"] == 900
    assert rec[union] == 400 + 100 + 50
    if total:
        assert rec[total] == 300 + 300 + 100 + 250   # as stamped, unclipped
        assert rec[total] >= rec[union]
    assert all(row["round"] != r - 1
               for row in ffi.round_summary()["rounds"])
    _interval(ffi, stage, r - 1, 0, 50)     # a round nobody enqueued
    _drive_round(ffi, r + 2, parts=1)
    _drive_round(ffi, r + 5, parts=1)
    assert all(row["round"] != r - 1
               for row in ffi.round_summary()["rounds"])


def test_feed_wait_is_the_time_with_no_partition_open():
    """One partition, a gap, then two at once:
    ``feed_wait_us`` and the time with a partition open make ``elapsed_us``."""
    from byteps_tpu.core import ffi

    r = 5_200_000
    ffi.round_track("enq", r, 0, 0, _T)
    ffi.round_track("done", r, 0, 0, _T + 1000)    # open 0..1000
    ffi.round_track("enq", r, 0, 0, _T + 1500)     # nothing open for 500
    ffi.round_track("enq", r, 0, 0, _T + 1600)
    ffi.round_track("done", r, 0, 0, _T + 2000)
    ffi.round_track("done", r, 0, 0, _T + 2500)    # open 1500..2500
    rec = _finalized(ffi, r)
    any_open = 1000 + 1000
    assert rec["elapsed_us"] == 2500 and rec["parts"] == 3
    assert rec["feed_wait_us"] == 500
    assert rec["feed_wait_us"] + any_open == rec["elapsed_us"]


def test_a_round_without_durations_reports_zeros():
    """Enqueue, frames and done only: every span and resource field is
    there and reads 0."""
    from byteps_tpu.core import ffi

    r = 5_300_000
    ffi.round_track("enq", r, 0, 0, _T)
    ffi.round_track("frame", r)
    ffi.round_track("push", r, 0, 64, _T + 10)     # a push of no length
    ffi.round_track("done", r, 0, 0, _T + 40)
    rec = _finalized(ffi, r)
    assert rec["elapsed_us"] == 40 and rec["feed_wait_us"] == 0
    for name in _SPANS + ("server_us", "push_thread_sum_us",
                          "send_blocked_sum_us", "recv_thread_sum_us"):
        assert rec[name] == 0, name


def test_an_ack_from_an_old_server_reads_as_all_wire():
    """``server_us`` rides the push ack as a duration; 0, from a server that
    sends nothing there, leaves the whole push to wire and van."""
    from byteps_tpu.core import ffi

    r = 5_400_000
    ffi.round_track("enq", r, 0, 0, _T)
    ffi.round_track("push", r, 900, 64, _T + 1000)
    ffi.round_track("sum", r, 0, 0, _T + 1000)
    ffi.round_track("server", r, 0, 0, _T + 1000)
    ffi.round_track("done", r, 0, 0, _T + 1200)
    rec = _finalized(ffi, r)
    assert rec["server_us"] == rec["server_span_us"] == 0
    assert rec["push_us"] - rec["server_us"] == 900 == rec["wire_ack_us"]
    # and a new one: the residence lies inside the push it came back on
    r += 10
    ffi.round_track("enq", r, 0, 0, _T)
    ffi.round_track("push", r, 900, 64, _T + 1000)
    ffi.round_track("server", r, 600, 0, _T + 1000)
    ffi.round_track("done", r, 0, 0, _T + 1200)
    rec = _finalized(ffi, r)
    assert rec["server_us"] == rec["server_span_us"] == 600
    assert rec["server_span_us"] <= rec["push_span_us"] == 900


def test_spans_stay_off_the_heartbeat_wire():
    """``RoundBusy`` is local, like the stamps: the wire element keeps its
    80 bytes and version 1, and a fleet record has none of its fields."""
    from byteps_tpu.core import ffi

    assert _REC.size == 80 and _VERSION == 1
    assert ffi.round_ingest(_pack_summary(48, [_pack_rec(12)]))
    assert not ffi.round_ingest(_pack_summary(48, [_pack_rec(13)],
                                              version=2))
    fleet_rec = ffi.round_summary()["fleet"]["48"]["last"]
    assert fleet_rec["round"] == 12
    assert not set(_SPANS) & set(fleet_rec)
    assert "server_us" not in fleet_rec


def test_a_long_interval_list_is_merged_in_place():
    """20,000 durations of one stage in one round (the list is merged at
    8,192): sum and union as if all had been kept."""
    from byteps_tpu.core import ffi

    r, n = 5_500_000, 20_000
    ffi.round_track("enq", r, 0, 0, _T)
    for k in range(n):      # 30 us every 50: no two touch
        ffi.round_track("pull", r, 30, 0, _T + 50 * k + 30)
    ffi.round_track("done", r, 0, 0, _T + 50 * n)
    rec = _finalized(ffi, r)
    assert rec["pull_us"] == rec["pull_span_us"] == 30 * n
    assert rec["elapsed_us"] == 50 * n


# --- classification boundaries (pure python) --------------------------------

def _rec(parts=4, queue=0, comp=0, push=0, sum_us=0, pull=0, dec=0,
         wire_msgs=0, fused=0, retries=0, parked=0, wire_bytes=0,
         round_no=10):
    return {"round": round_no, "parts": parts, "queue_us": queue,
            "comp_us": comp, "push_us": push, "sum_us": sum_us,
            "pull_us": pull, "dec_us": dec, "wire_bytes": wire_bytes,
            "wire_msgs": wire_msgs, "fused_frames": fused,
            "retries": retries, "parked": parked}


def test_classify_wire_bound():
    w = {n: _rec(push=100_000, sum_us=5_000, pull=10_000)
         for n in ("3", "4")}
    rep = insight.classify(w)
    assert rep["state"] == "wire-bound"
    assert rep["dominant"] == "wire_ack"


def test_classify_sum_bound():
    w = {n: _rec(push=100_000, sum_us=90_000, pull=10_000)
         for n in ("3", "4")}
    rep = insight.classify(w)
    assert rep["state"] == "sum-bound"
    assert rep["dominant"] == "server_sum"


def test_classify_straggler_skewed_outranks_dominance():
    """A paced rank's inflated push wall flags skew even though the
    fleet's dominant stage is (necessarily) wire_ack."""
    w = {"3": _rec(push=8_000, sum_us=1_000, pull=2_000),
         "4": _rec(push=900_000, sum_us=1_000, pull=2_000)}
    rep = insight.classify(w)
    assert rep["state"] == "straggler-skewed"
    assert rep["stragglers"] == ["4"]


def test_classify_retry_degraded_outranks_everything():
    w = {"3": _rec(push=8_000, sum_us=1_000, retries=0),
         "4": _rec(push=900_000, sum_us=1_000, retries=3)}
    rep = insight.classify(w)
    assert rep["state"] == "retry-degraded"


def test_classify_healthy_when_nothing_dominates():
    w = {n: _rec(queue=20_000, comp=20_000, push=45_000, sum_us=22_000,
                 pull=20_000, dec=20_000) for n in ("3", "4")}
    rep = insight.classify(w)
    assert rep["state"] == "healthy"


def test_classify_sub_floor_skew_stays_quiet():
    """Loopback microsecond skew is noise, not a straggler (absolute
    floor, mirroring monitor.top)."""
    w = {"3": _rec(parts=4, push=200), "4": _rec(parts=4, push=3_000)}
    rep = insight.classify(w)
    assert rep["state"] != "straggler-skewed"


def test_classify_idle_fleet():
    rep = insight.classify({})
    assert rep["state"] == "healthy" and rep["dominant"] == "idle"


def test_dominant_stage_and_breakdown():
    rec = _rec(queue=10, comp=20, push=100, sum_us=60, pull=30, dec=5)
    bd = insight.stage_breakdown(rec)
    assert bd["wire_ack"] == 40 and bd["server_sum"] == 60
    stage, share = insight.dominant_stage(rec)
    assert stage == "server_sum"
    assert share == pytest.approx(60 / 165)


def test_hints_name_the_knob():
    # wire-bound, unfused small messages -> fusion knob by name
    fleet = insight.merge_recs(
        [_rec(parts=4, push=100_000, sum_us=5_000, wire_msgs=64)] * 2)
    hs = insight.hints("wire-bound", fleet)
    assert any("BYTEPS_FUSION_BYTES" in h for h in hs)
    # sum-bound -> engine threads
    hs = insight.hints("sum-bound", fleet)
    assert any("BYTEPS_SERVER_ENGINE_THREAD" in h for h in hs)
    # queue-dominant rides along regardless of state
    fleet_q = insight.merge_recs([_rec(queue=500_000, push=100_000)])
    hs = insight.hints("healthy", fleet_q)
    assert any("BYTEPS_SCHEDULING_CREDIT" in h for h in hs)


def test_queue_hint_sends_the_reader_to_the_bound_stage_first():
    """ISSUE 48: the default credit is sized to keep the push thread busy,
    so a dominant queue wait is the bound stage's doing unless
    ``credit_blocked_us`` says the credit refused; the hint no longer
    opens with "raise the credit"."""
    fleet_q = insight.merge_recs([_rec(queue=500_000, push=100_000)])
    hint = [h for h in insight.hints("healthy", fleet_q)
            if "scheduled-queue" in h][0]
    assert hint.index("bound stage") < hint.index("credit_blocked_us") \
        < hint.index("BYTEPS_SCHEDULING_CREDIT")
    assert "ten partitions" in hint


def test_regressions_need_baseline_and_blowout():
    fleet = {
        "3": {"role": 2, "updates": 10, "ewma_wall_us": 10_000.0,
              "last": _rec(push=50_000)},          # 5x the baseline
        "4": {"role": 2, "updates": 10, "ewma_wall_us": 10_000.0,
              "last": _rec(push=11_000)},          # within noise
        "5": {"role": 2, "updates": 1, "ewma_wall_us": 1.0,
              "last": _rec(push=50_000)},          # baseline too young
    }
    assert insight.regressions(fleet) == ["3"]


def test_analyze_full_snapshot_shape():
    """analyze() over a scheduler-shaped snapshot: state + hints +
    regressions + the rounds the fleet table holds."""
    snap = {
        "on": True, "role": 0, "node_id": 0,
        "last": None, "rounds": [],
        "fleet": {
            "3": {"role": 2, "updates": 5, "ewma_wall_us": 100_000.0,
                  "last": _rec(push=100_000, sum_us=5_000,
                               wire_msgs=64)},
            "4": {"role": 2, "updates": 5, "ewma_wall_us": 100_000.0,
                  "last": _rec(push=100_000, sum_us=5_000,
                               wire_msgs=64)},
            "1": {"role": 1, "updates": 5, "ewma_wall_us": 5_000.0,
                  "last": _rec(sum_us=5_000)},  # server: not a worker
        },
        "fleet_rounds": {"10": {"3": _rec(), "4": _rec()}},
    }
    rep = insight.analyze(snap)
    assert rep["state"] == "wire-bound"
    assert not rep["local_only"]
    assert sorted(rep["workers"]) == ["3", "4"]
    assert rep["rounds_seen"] == [10]
    assert rep["hints"]


def test_analyze_falls_back_to_local_ring():
    snap = {"on": True, "role": 2, "node_id": 3,
            "last": _rec(push=100_000, sum_us=80_000), "rounds": [],
            "fleet": {}, "fleet_rounds": {}}
    rep = insight.analyze(snap)
    assert rep["local_only"]
    assert rep["state"] == "sum-bound"

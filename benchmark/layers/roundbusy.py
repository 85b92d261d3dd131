"""Layer: C core (``byteps_tpu/core/csrc`` via ``core/ffi.py``): which resource
of the PS round was busy, as elapsed time.

Beside a round's per-partition sums (``push_us``: ≈ 281 partitions' time added
up) and its stamps (``round.py``), ``ffi.round_summary()`` keeps every duration
the core tracks as an interval on its own clock and reduces each kind to the
length of the **union** over the round (``csrc/roundstats.h::RoundBusy``): the
elapsed time during which at least one partition was there. Medians over the
rounds completed inside the window, as ``round.py`` takes them:

``roundbusy.feed_wait_ms``       first enqueue to last pull landed with no
                                 partition enqueued and unfinished: the core
                                 waiting for ``jax/ps.py``'s bridge.
``roundbusy.credit_blocked_ms``  the scheduled queue's top refused for credit.
``roundbusy.push_thread_ms``     a push thread between its pop and the frame
                                 handed to the van.
``roundbusy.send_blocked_ms``    inside ``writev`` (or the shm ring's put) for a
                                 push or pull request.
``roundbusy.server_ms``          a push frame resident in the server (received
                                 whole to its ack), as the ack reports it.
``roundbusy.recv_thread_ms``     an ack or pull-response callback running.
``roundbusy.van_recv_ms``        the van's receive thread between a pull
                                 response's header and its payload read whole.

``STAGES`` names every kind with its union and its sum; sum ÷ union is the
mean depth (for ``push``: partitions in flight, against the credit), union ÷
``elapsed_us`` the share of the round it was busy. A C core from before the
fields reports nothing.

    python3 benchmark/layers/roundbusy.py <run's stderr | saved round_summary> [--trace-dir DIR]

prints that table — from the medians and maxima on a run's diagnostics line,
or from the rows of a saved ``ffi.round_summary()`` (the monitor endpoint's
``/rounds``; ``--last N`` rows) with every row held to the invariants — and,
with the capture of a traced run (``.benchmark_out/<cell>/trace``), each
``bps.ps.push_pull`` span's round mapped onto the capture's clock through the
span's ``mono_ns`` and how far inside the span its two ends lie.
"""

import json
import os
import statistics
import sys

LAYER = "C core"
# kind -> (its union, its sum) among a round row's fields
STAGES = {
    "queue": ("queue_span_us", "queue_us"),
    "comp": ("comp_span_us", "comp_us"),
    "push": ("push_span_us", "push_us"),
    "sum": ("sum_span_us", "sum_us"),
    "pull": ("pull_span_us", "pull_us"),
    "dec": ("dec_span_us", "dec_us"),
    "credit_blocked": ("credit_blocked_us", None),
    "push_thread": ("push_thread_us", "push_thread_sum_us"),
    "send_blocked": ("send_blocked_us", "send_blocked_sum_us"),
    "server": ("server_span_us", "server_us"),
    "recv_thread": ("recv_thread_us", "recv_thread_sum_us"),
    "van_recv": ("van_recv_us", None),
}
FIELDS = {"roundbusy.feed_wait_ms": "feed_wait_us",
          "roundbusy.credit_blocked_ms": "credit_blocked_us",
          "roundbusy.push_thread_ms": "push_thread_us",
          "roundbusy.send_blocked_ms": "send_blocked_us",
          "roundbusy.server_ms": "server_span_us",
          "roundbusy.recv_thread_ms": "recv_thread_us",
          "roundbusy.van_recv_ms": "van_recv_us"}
METRICS = {name: {"unit": "ms", "better": "lower",
                  "source": "program_counter", "moves": "step_ms_p50"}
           for name in FIELDS}
PUSH_PULL = "bps.ps.push_pull"


def window_rounds(counters) -> list:
    """The rows of the rounds completed inside the window; none where the
    ring (256 rounds) has lost some of them."""
    if "round_summary_after" not in counters:
        return []
    summary = counters["round_summary_after"]
    n = (summary["completed_total"]
         - counters["round_summary_before"]["completed_total"])
    return summary["rounds"][-n:] if 0 < n <= len(summary["rounds"]) else []


def read(run):
    rounds = window_rounds(run.counters)
    if not rounds:
        return {}
    return {name: statistics.median(r[field] for r in rounds) / 1e3
            for name, field in FIELDS.items() if field in rounds[0]}


def table(row) -> dict:
    """{kind: sum, union, depth, share of ``elapsed_us``} of one round row
    (or of a row of medians), in milliseconds; ``feed_wait`` has no sum."""
    elapsed = row["elapsed_us"]
    out = {"elapsed_ms": elapsed / 1e3}
    for kind, (union, total) in STAGES.items():
        if union not in row:
            continue
        entry = {"union_ms": row[union] / 1e3,
                 "share": row[union] / elapsed if elapsed else None}
        if total:
            entry["sum_ms"] = row[total] / 1e3
            entry["depth"] = row[total] / row[union] if row[union] else None
        out[kind] = entry
    if "feed_wait_us" in row:
        out["feed_wait"] = {
            "union_ms": row["feed_wait_us"] / 1e3,
            "share": row["feed_wait_us"] / elapsed if elapsed else None}
    if "push_window_us" in row and "push_span_us" in row:
        out["push_wire_empty_ms"] = (
            row["push_window_us"] - row["push_span_us"]) / 1e3
    return out


def violations(rounds) -> list:
    """What a worker's round rows must hold to, checked: every union and
    ``feed_wait_us`` within ``[0, elapsed_us]``, every sum at least its
    union, the server's residence inside the pushes it came back on."""
    bad = []
    for r in rounds:
        for union, total in STAGES.values():
            if union not in r:
                continue
            if not 0 <= r[union] <= r["elapsed_us"]:
                bad.append((r["round"], union, r[union]))
            if total and r[total] < r[union]:
                bad.append((r["round"], total, r[total]))
        if not 0 <= r["feed_wait_us"] <= r["elapsed_us"]:
            bad.append((r["round"], "feed_wait_us", r["feed_wait_us"]))
        if r["server_span_us"] > r["push_span_us"]:
            bad.append((r["round"], "server_span_us", r["server_span_us"]))
    return bad


def align(rounds, spans) -> list:
    """One entry per ``bps.ps.push_pull`` span ``(start_ns, duration_ns,
    mono_ns)``: the round whose first enqueue falls inside it, placed on the
    capture's clock. ``mono_ns`` is ``CLOCK_MONOTONIC`` — the core's
    ``NowUs()`` — read at the span's start, so ``start_us * 1000 - mono_ns``
    is where the round begins counted from the span's start, with no sampled
    anchor. Margins in ms: the round's start after the span's, its end
    before the span's; negative means outside. A span with no round of the
    list is left out."""
    out = []
    for start_ns, dur_ns, mono_ns in spans:
        inside = [r for r in rounds
                  if mono_ns <= r["start_us"] * 1000 <= mono_ns + dur_ns]
        if not inside:
            continue
        r = inside[0]
        begin = r["start_us"] * 1000 - mono_ns          # from the span's start
        out.append({
            "round": r["round"],
            "round_start_ns": start_ns + begin,
            "round_end_ns": start_ns + begin + r["elapsed_us"] * 1000,
            "start_margin_ms": begin / 1e6,
            "end_margin_ms": (dur_ns - begin - r["elapsed_us"] * 1000) / 1e6})
    return out


def push_pull_spans(xplane_path: str) -> list:
    """``(start_ns, duration_ns, mono_ns)`` of every ``bps.ps.push_pull`` of
    a capture, in time order."""
    import jax

    data = jax.profiler.ProfileData.from_file(xplane_path)
    return sorted(
        (int(ev.start_ns), int(ev.duration_ns),
         int({k: v for k, v in ev.stats}["mono_ns"]))
        for plane in data.planes for line in plane.lines
        for ev in line.events if ev.name == PUSH_PULL)


def _load(path: str) -> dict:
    """A saved round summary (one JSON object with ``rounds``), or the
    diagnostics line out of a run's stderr."""
    with open(path, errors="replace") as f:
        text = f.read()
    for line in reversed(text.splitlines()):
        if line.startswith("{") and ('"rounds"' in line
                                     or '"round_medians_us"' in line):
            return json.loads(line)
    return json.loads(text)


def main(argv) -> int:
    import argparse

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from benchmark.lib import trace_reduce

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("source")
    ap.add_argument("--trace-dir")
    ap.add_argument("--last", type=int, default=0)
    args = ap.parse_args(argv)
    found = _load(args.source)
    out = {}
    rounds = found.get("rounds")
    if isinstance(rounds, list):
        rounds = rounds[-args.last:]
        keys = [k for k in rounds[0] if k != "round"]
        out["rounds"] = len(rounds)
        out["median"] = table({k: statistics.median(r[k] for r in rounds)
                               for k in keys})
        out["max"] = table({k: max(r[k] for r in rounds) for k in keys})
        out["violations"] = violations(rounds)
    else:
        rounds = None
        out["rounds"] = found["rounds"]
        out["median"] = table(found["round_medians_us"])
        out["max"] = table(found["round_max_us"])
    if args.trace_dir:
        if rounds is None:
            raise SystemExit("the alignment needs the rounds' rows: a saved "
                             "round summary, not a diagnostics line")
        out["alignment"] = align(rounds, push_pull_spans(
            trace_reduce.find_xplane(args.trace_dir)))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

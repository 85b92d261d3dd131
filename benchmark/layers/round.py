"""Layer: C core (``byteps_tpu/core/csrc`` via ``core/ffi.py``): the round as
elapsed time.

``ffi.round_summary()`` keeps, beside each round's per-partition sums, six
stamps on the C core's own clock (``csrc/roundstats.h::RoundSpan``): the first
enqueue, the last pull landed, and for pushes and pulls the earliest issue and
the latest completion. Medians over the rounds completed inside the window (the
ring keeps 256: a window with more reports nothing rather than a part):

``round.elapsed_ms``      first enqueue to last pull landed. Not the
                          summary's ``wall_us``, which is partition-time.
``round.push_window_ms``  earliest push issued to latest push acknowledged.
``round.pull_window_ms``  earliest pull issued to latest response.

Whether the two windows overlap is in each round's ``push_offset_us`` /
``pull_offset_us`` (from the first enqueue). A C core from before the stamps
reports nothing."""

import statistics

LAYER = "C core"
FIELDS = {"round.elapsed_ms": "elapsed_us",
          "round.push_window_ms": "push_window_us",
          "round.pull_window_ms": "pull_window_us"}
METRICS = {name: {"unit": "ms", "better": "lower",
                  "source": "program_counter", "moves": "step_ms_p50"}
           for name in FIELDS}


def read(run):
    c = run.counters
    if "round_summary_after" not in c:
        return {}
    summary = c["round_summary_after"]
    n = (summary["completed_total"]
         - c["round_summary_before"]["completed_total"])
    if not 0 < n <= len(summary["rounds"]):
        return {}
    rounds = summary["rounds"][-n:]
    return {name: statistics.median(r[field] for r in rounds) / 1e3
            for name, field in FIELDS.items() if field in rounds[0]}

"""Layer: C core (``byteps_tpu/core/csrc`` via ``core/ffi.py``).

``ccore.round_wall_ms``: the median ``wall_us`` of the window's rounds in
``ffi.round_summary()``, on the C core's own clock. Mind what the C core
calls wall: ``RoundWallUs`` (csrc/roundstats.h) is queue + comp + push + pull
+ dec, each a sum over the round's partitions — partition-time, not elapsed
time. With ~120 partitions of a 498 MB tree in flight it reads ~22 s for a
round that takes ~0.3 s (my chip run, PR 22). It moves with the work the C
core does per round and is the only per-round figure readable from outside
today; the tracing issue replaces it with an elapsed time on the profiler's
clock. The ring keeps 256 rounds: a window with more reports nothing rather
than a part.
``ccore.push_bytes_per_step``: growth of ``bps_push_bytes_total`` over the
window / steps, an exact count."""

import statistics

LAYER = "C core"
METRICS = {
    "ccore.round_wall_ms": {"unit": "ms", "better": "lower",
                            "source": "program_counter",
                            "moves": "step_ms_p50"},
    "ccore.push_bytes_per_step": {"unit": "bytes", "better": "lower",
                                  "source": "program_counter",
                                  "moves": "tokens_per_s_per_chip"},
}


def read(run):
    c, steps = run.counters, run.window.completed
    if "round_summary_after" not in c or not steps:
        return {}
    out = {"ccore.push_bytes_per_step":
           (c["push_bytes_after"] - c["push_bytes_before"]) / steps}
    summary = c["round_summary_after"]
    n = (summary["completed_total"]
         - c["round_summary_before"]["completed_total"])
    if 0 < n <= len(summary["rounds"]):
        out["ccore.round_wall_ms"] = statistics.median(
            r["wall_us"] for r in summary["rounds"][-n:]) / 1e3
    return out

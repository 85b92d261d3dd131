"""Layer: C core (``byteps_tpu/core/csrc`` via ``core/ffi.py``).

``ccore.push_bytes_per_step``: growth of ``bps_push_bytes_total`` over the
window / steps, an exact count. The round's times are ``round.py``'s:
``ffi.round_summary()``'s ``wall_us`` is partition-time, not elapsed time
(queue + comp + push + pull + dec, each summed over the round's partitions),
and read 19% "worse" for a step that got 8.6% faster (ledger, PR 25), so the
metric that reported it was retired in PR 26."""

LAYER = "C core"
METRICS = {
    "ccore.push_bytes_per_step": {"unit": "bytes", "better": "lower",
                                  "source": "program_counter",
                                  "moves": "tokens_per_s_per_chip"},
}


def read(run):
    c, steps = run.counters, run.window.completed
    if "push_bytes_after" not in c or not steps:
        return {}
    return {"ccore.push_bytes_per_step":
            (c["push_bytes_after"] - c["push_bytes_before"]) / steps}

"""Layer: expert share, a quarter of the experts at top-8
(``byteps_tpu/parallel/moe.py::dropless_moe_ffn`` with a softmax gate over
64 experts, top-8 renormalised, told which 16 it holds, no shared expert,
inside ``models/kimi_linear.py::KimiSparseMoe`` as ``models/mellum.py``
stacks it: four expert layers, 8 assignments a token sorted, of which two
are held at even routing — ``held_row_bound`` = half of all T k rows a pass,
16 grouped matmuls of about T / 8 rows x 2304 x 896).

``layers/eshare.py``'s reader under this cell's names — ``eshare.*``'s
``workloads`` lists are not this PR's to append to. ``mmoe.gmm_ms``,
``mmoe.route_ms``, ``mmoe.layer_share_pct``, ``mmoe.gmm_roofline_pct`` and
``mmoe.held_load`` are ``eshare.*`` of those names, word for word (the
``%ragged-dot`` kernels by name; the scopes ``bps.moe.route`` and
``bps.moe.experts``; the needed operations and bytes from the rows the
probe counted at the held experts, their weights only): its ``setup`` and
``read`` are called, nothing of it is copied. One figure is this reader's
own:

``mmoe.compact_share_pct`` (program counter): the share of the expert
                    layers whose held assignments fit ``held_row_bound``,
                    the layers that take one pass over their rows and not
                    several (the probe's gauge ``bps_moe_compact_share``,
                    ``publish_moe_stats(..., held=...)``, first batch, the
                    run's own weights). 100 wherever the routing sends the
                    held experts at most twice their even part.

``mmoe.held_load`` and ``mmoe.compact_share_pct`` are readings of the
initialisation: the probe runs once, before the first step, so both read
about 1.0 and 100 whatever the window does. While this cell's pool of 16
batches cycled, the window did move — the model memorised them, the router
sent the held quarter over half of the assignments within 44 steps and
every layer came to a second pass, + 70 ms each (PERF.md section 6, PR 58)
— and no figure of this reader could show it. Since PR 66 the pool is 128,
no batch is seen twice and the held load ends a window where it began;
``layers/share.py`` reads the same probe at the window's end
(``share.held_load_end``, by layer on the diagnostics line: a second pass
comes where a layer's passes 2.0), and ``mmoe.compact_share_pct``, 100.0 on
every line of the ledger, left ``BENCHMARK.json`` for it: it is still
computed here and dropped from the line.

A program without the kernels, the scopes or the collection reports nothing.
"""

LAYER = "expert share, a quarter of the experts at top-8"
METRICS = {
    "mmoe.gmm_ms": {"unit": "ms", "better": "lower",
                    "source": "device_trace", "moves": "step_ms_p50"},
    "mmoe.route_ms": {"unit": "ms", "better": "lower",
                      "source": "device_trace", "moves": "step_ms_p50"},
    "mmoe.layer_share_pct": {"unit": "%", "better": "lower",
                             "source": "device_trace",
                             "moves": "step_ms_p50"},
    "mmoe.gmm_roofline_pct": {"unit": "%", "better": "higher",
                              "source": "device_trace", "moves": "mfu_pct"},
    "mmoe.held_load": {"unit": "ratio", "better": "lower",
                       "source": "program_counter",
                       "moves": "tokens_per_s_per_chip"},
    "mmoe.compact_share_pct": {"unit": "%", "better": "higher",
                               "source": "program_counter",
                               "moves": "step_ms_p50"},
}


def setup(run):
    from benchmark.layers import eshare

    eshare.setup(run)


def read(run):
    from benchmark.layers import eshare

    out = {"mmoe." + name.partition(".")[2]: value
           for name, value in eshare.read(run).items()}
    compact = run.probes.get("bps_moe_compact_share")
    if compact is not None:
        out["mmoe.compact_share_pct"] = 100.0 * compact
    return out

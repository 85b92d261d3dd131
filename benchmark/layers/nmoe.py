"""Layer: expert share, many small experts (``byteps_tpu/parallel/moe.py::
dropless_moe_ffn`` with a softmax gate over 512 experts, top-10
renormalised, told which 32 it holds, plus a shared expert under a sigmoid
gate, inside ``models/kimi_linear.py::KimiSparseMoe`` as ``models/
qwen3_next.py`` stacks it: four expert layers, 163,840 assignments each
sorted, 32 grouped matmuls of about 320 rows x 512).

Two readers under this cell's names — ``eshare.*``'s and ``smoe.*``'s
``workloads`` lists are not this PR's to append to. ``nmoe.gmm_ms``,
``nmoe.route_ms``, ``nmoe.gmm_roofline_pct`` and ``nmoe.held_load`` are
``layers/eshare.py``'s ``eshare.*`` of those names, word for word (the
``%ragged-dot`` kernels by name; the needed operations and bytes from the
rows the probe counted at the held experts, their weights only), and
``nmoe.layer_share_pct`` is ``layers/smoe.py``'s ``smoe.layer_share_pct``
(``bps.moe.route``, ``bps.moe.experts`` and ``bps.moe.shared``, the gated
shared expert, over the capture's program time): their ``setup`` and
``read`` are called, nothing of them is copied.

A program without the kernels, the scopes or the collection reports nothing.
"""

LAYER = "expert share, many small experts"
METRICS = {
    "nmoe.route_ms": {"unit": "ms", "better": "lower",
                      "source": "device_trace", "moves": "step_ms_p50"},
    "nmoe.gmm_ms": {"unit": "ms", "better": "lower",
                    "source": "device_trace", "moves": "step_ms_p50"},
    "nmoe.layer_share_pct": {"unit": "%", "better": "lower",
                             "source": "device_trace",
                             "moves": "step_ms_p50"},
    "nmoe.gmm_roofline_pct": {"unit": "%", "better": "higher",
                              "source": "device_trace", "moves": "mfu_pct"},
    "nmoe.held_load": {"unit": "ratio", "better": "lower",
                       "source": "program_counter",
                       "moves": "tokens_per_s_per_chip"},
}
FROM_ESHARE = ("route_ms", "gmm_ms", "gmm_roofline_pct", "held_load")


def setup(run):
    from benchmark.layers import eshare

    eshare.setup(run)


def read(run):
    from benchmark.layers import eshare, smoe

    out = {"nmoe." + name.partition(".")[2]: value
           for name, value in eshare.read(run).items()
           if name.partition(".")[2] in FROM_ESHARE}
    share = smoe.read(run).get("smoe.layer_share_pct")
    if share is not None:
        out["nmoe.layer_share_pct"] = share
    return out

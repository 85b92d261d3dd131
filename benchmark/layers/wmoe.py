"""Layer: expert share, windowed stack (``byteps_tpu/parallel/moe.py::
dropless_moe_ffn`` with a sigmoid gate and no selection bias, told which
experts it holds, plus the shared expert, inside ``models/kimi_linear.py::
KimiSparseMoe`` as ``models/laguna.py`` stacks it: four expert layers).

``layers/smoe.py``'s reader under this cell's names — ``smoe.*``'s
``workloads`` lists are not this PR's to append to. ``wmoe.route_ms``,
``wmoe.layer_share_pct`` and ``wmoe.held_load`` are ``smoe.route_ms``,
``smoe.layer_share_pct`` and ``smoe.held_load`` there, word for word: its
``setup`` and ``read`` are called, nothing of it is copied
(``layers/lmoe.py`` does the same for the latent stack).

A program without the scopes or the collection reports nothing.
"""

LAYER = "expert share, windowed stack"
METRICS = {
    "wmoe.route_ms": {"unit": "ms", "better": "lower",
                      "source": "device_trace", "moves": "step_ms_p50"},
    "wmoe.layer_share_pct": {"unit": "%", "better": "lower",
                             "source": "device_trace",
                             "moves": "step_ms_p50"},
    "wmoe.held_load": {"unit": "ratio", "better": "lower",
                       "source": "program_counter",
                       "moves": "tokens_per_s_per_chip"},
}


def setup(run):
    from benchmark.layers import smoe

    smoe.setup(run)


def read(run):
    from benchmark.layers import smoe

    return {"wmoe." + name.partition(".")[2]: value
            for name, value in smoe.read(run).items()}

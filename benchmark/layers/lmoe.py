"""Layer: expert share, latent stack (``byteps_tpu/parallel/moe.py::
dropless_moe_ffn`` with a sigmoid gate, told which experts it holds, plus
the shared expert, inside ``models/kimi_linear.py::KimiSparseMoe`` as
``models/joyai.py`` stacks it: four main layers and the MTP module's).

``layers/smoe.py``'s reader under this cell's names — ``smoe.*``'s
``workloads`` lists are not this PR's to append to. ``lmoe.route_ms``,
``lmoe.layer_share_pct`` and ``lmoe.held_load`` are ``smoe.route_ms``,
``smoe.layer_share_pct`` and ``smoe.held_load`` there, word for word: its
``setup`` and ``read`` are called, nothing of it is copied. The module's
expert layer is inside ``bps.mtp`` and so in ``mtp.module_ms`` too.

A program without the scopes or the collection reports nothing.
"""

LAYER = "expert share, latent stack"
METRICS = {
    "lmoe.route_ms": {"unit": "ms", "better": "lower",
                      "source": "device_trace", "moves": "step_ms_p50"},
    "lmoe.layer_share_pct": {"unit": "%", "better": "lower",
                             "source": "device_trace",
                             "moves": "step_ms_p50"},
    "lmoe.held_load": {"unit": "ratio", "better": "lower",
                       "source": "program_counter",
                       "moves": "tokens_per_s_per_chip"},
}


def setup(run):
    from benchmark.layers import smoe

    smoe.setup(run)


def read(run):
    from benchmark.layers import smoe

    return {"lmoe." + name.partition(".")[2]: value
            for name, value in smoe.read(run).items()}

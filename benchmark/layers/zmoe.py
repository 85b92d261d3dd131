"""Layer: expert share, few wide experts behind an MLP router
(``byteps_tpu/parallel/moe.py::dropless_moe_ffn`` given its logits by
``models/zaya.py::ZayaRouter`` — a down-projection, a mix with the block
before's state and a three-layer MLP — top-1 of 16 by the raw probability,
told which 8 it holds: five expert layers, 16,384 assignments each sorted,
8 grouped matmuls of about 1,024 rows x 2048 x 2048).

``layers/eshare.py``'s reader under this cell's names — ``eshare.*``'s
``workloads`` lists are not this PR's to append to. ``zmoe.gmm_ms``,
``zmoe.gmm_roofline_pct``, ``zmoe.layer_share_pct`` and ``zmoe.held_load``
are ``eshare.*`` of those names, word for word (the ``%ragged-dot`` kernels
by name; the needed operations and bytes from the rows the probe counted at
the held experts, their weights only): its ``setup`` and ``read`` are
called, nothing of it is copied. One scope is this layer's own:

``zmoe.router_ms``  what runs under ``bps.moe.router``: the router's
                    down-projection, the depth averaging and the MLP, in
                    float32 at the highest matmul precision, forward,
                    recomputed and backward.
``zmoe.route_ms``   ``eshare.route_ms`` less ``zmoe.router_ms``: the
                    reader there asks whether an op's scope holds
                    ``bps.moe.route``, and ``bps.moe.router`` does, so
                    its figure holds both (and ``zmoe.layer_share_pct``,
                    which is its share, counts the router in the layer,
                    where it belongs).

A program without the kernels, the scopes or the collection reports nothing.
"""

LAYER = "expert share, few wide experts behind an MLP router"
ROUTER = {"router": "bps.moe.router"}
METRICS = {
    "zmoe.router_ms": {"unit": "ms", "better": "lower",
                       "source": "device_trace", "moves": "step_ms_p50"},
    "zmoe.route_ms": {"unit": "ms", "better": "lower",
                      "source": "device_trace", "moves": "step_ms_p50"},
    "zmoe.gmm_ms": {"unit": "ms", "better": "lower",
                    "source": "device_trace", "moves": "step_ms_p50"},
    "zmoe.layer_share_pct": {"unit": "%", "better": "lower",
                             "source": "device_trace",
                             "moves": "step_ms_p50"},
    "zmoe.gmm_roofline_pct": {"unit": "%", "better": "higher",
                              "source": "device_trace", "moves": "mfu_pct"},
    "zmoe.held_load": {"unit": "ratio", "better": "lower",
                       "source": "program_counter",
                       "moves": "tokens_per_s_per_chip"},
}


def setup(run):
    from benchmark.layers import eshare

    eshare.setup(run)


def read(run):
    from benchmark.layers import eshare, kda

    out = {"zmoe." + name.partition(".")[2]: value
           for name, value in eshare.read(run).items()}
    if run.trace is None or "zmoe.route_ms" not in out:
        return out
    ops, _, steps = kda.capture_ms(run)
    router = kda.scoped_ms(ops, ROUTER, steps)
    if router:
        out["zmoe.router_ms"] = router["router"]
        out["zmoe.route_ms"] -= router["router"]
    return out

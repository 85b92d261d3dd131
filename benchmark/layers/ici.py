"""Layer: in-jit reduction (``byteps_tpu/jax/__init__.py::push_pull`` →
``parallel/hierarchical.py``).

From the device trace, first device: the time per step in which a collective
operation (all-reduce, reduce-scatter, all-gather, all-to-all,
collective-permute; asynchronous ones from start to done) was running, and
the part of it in which no other operation ran on that device."""

LAYER = "in-jit reduction"
METRICS = {
    "ici.collective_ms": {"unit": "ms", "better": "lower",
                          "source": "device_trace",
                          "moves": "tokens_per_s_per_chip"},
    "ici.exposed_ms": {"unit": "ms", "better": "lower",
                       "source": "device_trace",
                       "moves": "tokens_per_s_per_chip"},
}


def read(run):
    if run.trace is None or run.chips < 2:
        return {}
    return {"ici.collective_ms": 1e3 * run.trace["collective_s_per_step"],
            "ici.exposed_ms":
                1e3 * run.trace["exposed_collective_s_per_step"]}

"""Layer: step builders (``byteps_tpu/jax/training.py``).

From the device trace, first device, line ``XLA Modules``: how long the
step's programs ran per step and how many there were (1 in collective mode,
2 in PS mode: the gradient program and the apply program)."""

LAYER = "step builders"
METRICS = {
    "step.device_ms": {"unit": "ms", "better": "lower",
                       "source": "device_trace", "moves": "step_ms_p50"},
    "step.programs_per_step": {"unit": "count", "better": "lower",
                               "source": "device_trace",
                               "moves": "step_ms_p50"},
}


def read(run):
    if run.trace is None:
        return {}
    return {"step.device_ms": 1e3 * run.trace["program_s_per_step"],
            "step.programs_per_step": run.trace["programs_per_step"]}

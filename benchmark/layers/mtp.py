"""Layer: multi-token prediction (``models/joyai.py::MTPModule`` and the
second pass through the main head: DeepSeek-V3's module of depth 1).

From the device trace, first device, line ``XLA Ops``, per traced step
(``layers/kda.py::capture_ms`` reads the capture once for the cell's three
readers):

``mtp.module_ms``  everything under ``bps.mtp``: the two norms and ``W_eh``
                   (``bps.mtp.combine``; ``probes.mtp_combine_ms``), the
                   module's whole block and its pass through the head —
                   forward, recomputed and backward.
``mtp.head_ms``    of that, what runs under ``bps.lm.head``: the module's
                   pass through the main model's head and the cross-entropy,
                   in row blocks.
``mtp.share_pct``  ``mtp.module_ms`` over the time of the capture's programs
                   on ``XLA Modules``.

The module's block carries ``bps.mla.*`` and ``bps.moe.*`` inside
``bps.mtp``, so its ops are in ``qmla.*`` and ``eshare.*`` too: the three
prefixes overlap by that block and are not to be added up.

``bps_mtp_main_loss`` and ``bps_mtp_next2_loss`` (gauges, ``probes`` on the
diagnostics line) come from a probe before the window: the first batch
through the run's own weights with the ``"mtp_stats"`` collection mutable,
published by ``models/joyai.py::publish_mtp_stats``.

A program without the scope or the collection reports nothing.
"""

LAYER = "multi-token prediction"
SCOPE = "bps.mtp"
SCOPES = {"combine": "bps.mtp.combine", "head": "bps.lm.head"}
METRICS = {
    "mtp.module_ms": {"unit": "ms", "better": "lower",
                      "source": "device_trace", "moves": "step_ms_p50"},
    "mtp.head_ms": {"unit": "ms", "better": "lower",
                    "source": "device_trace", "moves": "step_ms_p50"},
    "mtp.share_pct": {"unit": "%", "better": "lower",
                      "source": "device_trace", "moves": "step_ms_p50"},
}


def setup(run):
    """The probe: the first batch's two mean losses, with the run's own
    weights."""
    stats_of = getattr(run.config, "layer_stats", None)
    if stats_of is None or not getattr(run.config, "FIRST", None):
        return
    try:
        from byteps_tpu.models import publish_mtp_stats
    except ImportError:            # a program without the module
        return
    stats = stats_of(run.cfg, run.rows // run.chips)   # one chip's batch
    if "mtp_stats" in stats:
        run.probes.update(publish_mtp_stats(stats["mtp_stats"]))


def read(run):
    if run.trace is None:
        return {}
    from benchmark.layers import kda

    ops, programs_ms, steps = kda.capture_ms(run)
    # an op goes to the first scope its tf_op holds: the module's own last
    ms = kda.scoped_ms([op for op in ops if SCOPE in op[1]],
                       {**SCOPES, "rest": SCOPE}, steps)
    if not ms or not programs_ms:
        return {}
    module_ms = sum(ms.values())
    run.probes["mtp_combine_ms"] = ms["combine"]
    return {"mtp.module_ms": module_ms, "mtp.head_ms": ms["head"],
            "mtp.share_pct": 100.0 * module_ms / programs_ms}

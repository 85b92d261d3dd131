"""Layer: shared-expert share (``byteps_tpu/parallel/moe.py::
dropless_moe_ffn`` with a sigmoid gate, told which experts it holds, plus
the shared expert, inside ``models/kimi_linear.py::KimiSparseMoe``).

The expert layer's reader for a chip that holds a share of the routed
experts beside a shared expert that every token passes. From the device
trace, first device, line ``XLA Ops``, per traced step (``layers/kda.py::
capture_ms`` reads the capture once for the cell's three readers):

``smoe.route_ms``        what runs under ``bps.moe.route``: the router, the
                         sigmoid gate, top-k, sort, the gather of all T k
                         rows (131,072 a layer here, of which the held
                         experts' expected 4,096 are computed), un-permute
                         and combine, and their gradients; the
                         ``ragged-dot-metadata`` helpers count here.
``smoe.layer_share_pct`` that, ``bps.moe.experts`` (the grouped matmuls and
                         their casts) and ``bps.moe.shared`` (the shared
                         expert; ``probes.smoe_shared_ms``) over the time of
                         the capture's programs on ``XLA Modules``.
``smoe.held_load`` (program counter): the assignments that reached the held
                         experts over their even part T k H / E, all layers
                         together, from a probe before the window — the
                         first batch through the run's own weights with the
                         ``"moe_stats"`` collection mutable
                         (``publish_moe_stats(..., held=...)``, gauge
                         ``bps_moe_held_load``; 1 at even routing).

A program without the scopes or the collection reports nothing.
"""

LAYER = "shared-expert share"
SCOPES = {"route": "bps.moe.route", "experts": "bps.moe.experts",
          "shared": "bps.moe.shared"}
METRICS = {
    "smoe.route_ms": {"unit": "ms", "better": "lower",
                      "source": "device_trace", "moves": "step_ms_p50"},
    "smoe.layer_share_pct": {"unit": "%", "better": "lower",
                             "source": "device_trace",
                             "moves": "step_ms_p50"},
    "smoe.held_load": {"unit": "ratio", "better": "lower",
                       "source": "program_counter",
                       "moves": "tokens_per_s_per_chip"},
}


def setup(run):
    """The probe: which experts the first batch's tokens reach, with the
    run's own weights, and how many of the assignments are held here."""
    stats_of = getattr(run.config, "layer_stats", None)
    if stats_of is None or not getattr(run.config, "FIRST", None):
        return
    from byteps_tpu.parallel.moe import publish_moe_stats

    stats = stats_of(run.cfg, run.rows // run.chips)   # one chip's batch
    if "moe_stats" in stats:
        run.probes.update(publish_moe_stats(stats["moe_stats"], held=(
            run.config.FIRST_EXPERT, run.cfg["num_local_experts"])))


def read(run):
    out = {"smoe.held_load": run.probes.get("bps_moe_held_load")}
    if run.trace is None:
        return out
    from benchmark.layers import kda

    ops, programs_ms, steps = kda.capture_ms(run)
    ms = kda.scoped_ms([
        (name, SCOPES["route"] if name.startswith("%ragged-dot-metadata")
         else tf_op, duration_ps) for name, tf_op, duration_ps in ops],
        SCOPES, steps)
    if not ms or not programs_ms:
        return out
    run.probes["smoe_shared_ms"] = ms["shared"]
    run.probes["smoe_experts_ms"] = ms["experts"]
    out["smoe.route_ms"] = ms["route"]
    out["smoe.layer_share_pct"] = 100.0 * sum(ms.values()) / programs_ms
    return out

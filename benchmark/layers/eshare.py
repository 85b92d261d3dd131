"""Layer: expert share (``byteps_tpu/parallel/moe.py::dropless_moe_ffn``
told which experts it holds, inside ``models/keye.py``).

The expert layer's reader (``layers/moe.py``) for a chip that holds a share
of the experts. ``moe.gmm_roofline_pct`` counts T k rows through all the
router's experts; a share computes the rows that fall to its own experts
and holds their weights only, so its operations and bytes are counted here,
from the rows the probe counted. ``layers/moe.py`` reads the capture;
nothing of it is copied.

``eshare.gmm_ms``, ``eshare.route_ms``, ``eshare.layer_share_pct``: as
``moe.gmm_ms``, ``moe.route_ms`` and ``moe.layer_share_pct`` (the
``%ragged-dot`` kernels by name, the scopes ``bps.moe.route`` and
``bps.moe.experts``, over the capture's program time), recomputation
included.
``eshare.gmm_roofline_pct``: the least time the chip could take for the
grouped matmuls the mathematics needs — gate, up and down, each forward,
dgrad and wgrad once, over the rows that reached the held experts (the
probe's count for the first batch, every layer), each operand once in bf16
with the held experts' weights only — over ``eshare.gmm_ms``. Rows beyond
the held groups and recomputed calls earn nothing.
``eshare.held_load`` (program counter): the assignments that reached the
held experts over their even part T k H / E, from the same probe
(``publish_moe_stats(..., held=...)``, gauge ``bps_moe_held_load``).

A program without the kernels, the scopes or the collection reports nothing.
"""

import os

LAYER = "expert share"
METRICS = {
    "eshare.gmm_ms": {"unit": "ms", "better": "lower",
                      "source": "device_trace", "moves": "step_ms_p50"},
    "eshare.route_ms": {"unit": "ms", "better": "lower",
                        "source": "device_trace", "moves": "step_ms_p50"},
    "eshare.layer_share_pct": {"unit": "%", "better": "lower",
                               "source": "device_trace",
                               "moves": "step_ms_p50"},
    "eshare.gmm_roofline_pct": {"unit": "%", "better": "higher",
                                "source": "device_trace",
                                "moves": "mfu_pct"},
    "eshare.held_load": {"unit": "ratio", "better": "lower",
                         "source": "program_counter",
                         "moves": "tokens_per_s_per_chip"},
}
CALLS = 9     # gate, up and down, each forward, dgrad and wgrad


def gmm_flops(held_rows: int, d: int, m: int) -> int:
    """``held_rows``: the assignments to held experts, all layers. Every
    call multiplies each through one d x m matrix."""
    return CALLS * 2 * held_rows * d * m


def gmm_bytes(held_rows: int, held: int, d: int, m: int, layers: int,
              operand_bytes: int = 2) -> int:
    """Every call reads two and writes one of: the rows at width d, the rows
    at width m, the held experts' d x m weights (or their gradient)."""
    return CALLS * operand_bytes * (held_rows * (d + m)
                                    + layers * held * d * m)


def gmm_roofline_pct(gmm_ms: float, cfg: dict, held_rows: int,
                     peaks: dict) -> float:
    d, m = cfg["hidden_size"], cfg["moe_intermediate_size"]
    least_s = max(
        gmm_flops(held_rows, d, m) / peaks["bf16_flops_per_s"],
        gmm_bytes(held_rows, cfg["num_local_experts"], d, m,
                  cfg["num_hidden_layers"]) / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (gmm_ms * 1e-3)


def setup(run):
    """The probe: which experts the first batch's tokens reach, with the
    run's own weights, and how many of the assignments are held here."""
    stats_of = getattr(run.config, "layer_stats", None)
    if stats_of is None:
        return
    import jax

    from byteps_tpu.parallel.moe import publish_moe_stats

    stats = stats_of(run.cfg, run.rows // run.chips)   # one chip's batch
    if "moe_stats" not in stats:
        return
    first, held = run.config.FIRST_EXPERT, run.cfg["num_local_experts"]
    run.probes.update(publish_moe_stats(stats["moe_stats"],
                                        held=(first, held)))
    run.probes["eshare_held_rows"] = int(sum(
        c[first:first + held].sum()
        for c in jax.tree_util.tree_leaves(stats["moe_stats"])))


def read(run):
    out = {"eshare.held_load": run.probes.get("bps_moe_held_load")}
    if run.trace is None:
        return out
    from benchmark.layers import moe
    from benchmark.lib import device, trace_reduce

    xplane = trace_reduce.find_xplane(os.path.join(run.out_dir, "trace"))
    steps = run.trace["steps"]
    ms = moe.split_ms(moe.scoped_ops(xplane, run.layout), steps)
    programs_ms = sum(d for _, _, d in moe.scoped_ops(
        xplane, run.layout, run.layout.module_line)) * 1e-9 / steps
    if not ms or not programs_ms:
        return out
    run.probes["eshare_experts_other_ms"] = ms["experts_other"]
    out["eshare.route_ms"] = ms["route"]
    out["eshare.layer_share_pct"] = 100.0 * sum(ms.values()) / programs_ms
    held_rows = run.probes.get("eshare_held_rows")
    if ms["gmm"] and held_rows:
        import jax

        out["eshare.gmm_ms"] = ms["gmm"]
        out["eshare.gmm_roofline_pct"] = gmm_roofline_pct(
            ms["gmm"], run.cfg, held_rows,
            device.peaks(jax.devices()[0].device_kind))
    return out

"""Layer: state-space layers (``byteps_tpu/parallel/linear_attention.py::
ssd_scan`` inside ``models/nemotron_h.py::Mamba2Mixer``: Mamba-2's selective
state-space recurrence as a chunked scan with no delta rule, 8 groups of
``B`` and ``C`` under 64 heads).

From the device trace, first device, line ``XLA Ops``, per traced step
(``layers/kda.py::capture_ms`` reads the capture once for the cell's
readers; its ``scoped_ms`` sums by scope, containers skipped), over every
Mamba-2 layer:

``ssm.scan_ms``   ``bps.ssm.scan``: the chunked recurrence — a chunk's masked
                  pairs, every chunk's share of the next state, the scan
                  over chunks, the read of a chunk's first state — forward,
                  the forward recomputed in the backward pass, and backward.
``ssm.prep_ms``   ``bps.ssm.prep``: the in-projection's epilogue — the
                  convolution with its bias, SiLU, softplus, the decay and
                  the cumulated log-decay — the same three ways.
``ssm.proj_ms``   ``bps.ssm.proj``: the in-projection (z, xBC in bf16; dt in
                  float32 at the highest precision) and the out-projection.
``ssm.layer_share_pct``  those three and ``bps.ssm.out`` (``D x``, the
                  ``SiLU(z)`` gate, the group norm; ``probes.ssm_out_ms``)
                  over the time of the capture's programs on ``XLA Modules``.
``ssm.scan_roofline_pct``  the least time the chip could take for what the
                  recurrence needs at these shapes, whatever implements it
                  — the larger of ``scan_flops`` over the peak bf16 rate and
                  ``scan_bytes`` over the peak HBM rate (``lib/peaks.json``)
                  — over ``ssm.scan_ms``. No chunk length is in either
                  count, so a later kernel or another chunk cannot make them
                  stale, and what a chunked form adds (a chunk's pairs, a
                  state a chunk) earns nothing.

By hand, one token of one head, state n, channels p, forward, 2 operations a
multiply-add: the decay of the state n p, the rank-one write 2 n p, ``S^T
C`` 2 n p: 5 n p = 40,960 at 128 x 64; forward once and backward twice that.
A step of 16,384 tokens, 64 heads, 4 layers: 515.4 GFLOP, 2.62 ms at the
peak. Bytes, a layer: B and C [tokens, 8, 128] (a group serves eight heads
and is read once), x and y [tokens, 64, 64], g and dt [tokens, 64], in
float32, each read or written once forward and once more backward: 2 x
16,384 x 4 x (2 x 1024 + 2 x 4096 + 128) = 1.359 GB, 5.44 GB for four
layers, 6.64 ms: the scan is bound by bandwidth on this count. (ISSUE 63
estimated 4.0 GB a step: it left the second pass over x and y out.)

``bps_ssm_min_chunk_log_decay`` (gauge, ``probes`` on the diagnostics line)
comes from a probe before the window: the first batch through the run's own
weights with the ``"ssm_stats"`` collection mutable, published by
``parallel/linear_attention.py::publish_kda_stats`` under this gauge's name.

A program without the scopes or the collection reports nothing.
"""

LAYER = "state-space layers"
SCOPES = {"scan": "bps.ssm.scan", "prep": "bps.ssm.prep",
          "proj": "bps.ssm.proj", "out": "bps.ssm.out"}
GAUGE = "bps_ssm_min_chunk_log_decay"
METRICS = {
    "ssm.scan_ms": {"unit": "ms", "better": "lower",
                    "source": "device_trace", "moves": "step_ms_p50"},
    "ssm.prep_ms": {"unit": "ms", "better": "lower",
                    "source": "device_trace", "moves": "step_ms_p50"},
    "ssm.proj_ms": {"unit": "ms", "better": "lower",
                    "source": "device_trace", "moves": "step_ms_p50"},
    "ssm.layer_share_pct": {"unit": "%", "better": "lower",
                            "source": "device_trace",
                            "moves": "step_ms_p50"},
    "ssm.scan_roofline_pct": {"unit": "%", "better": "higher",
                              "source": "device_trace", "moves": "mfu_pct"},
}


def scan_flops(tokens: int, heads: int, state: int, channels: int,
               layers: int) -> int:
    """The recurrence token by token, forward once and backward twice
    that (docstring)."""
    return 3 * layers * tokens * heads * 5 * state * channels


def scan_bytes(tokens: int, groups: int, heads: int, state: int,
               channels: int, layers: int) -> int:
    """B, C at the groups, x, y at the heads, g and dt one float a head,
    float32, forward and backward once each."""
    per_token = 4 * (2 * groups * state + 2 * heads * channels + 2 * heads)
    return layers * 2 * tokens * per_token


def scan_roofline_pct(scan_ms: float, cfg: dict, tokens: int,
                      peaks: dict) -> float:
    heads, groups = cfg["mamba_num_heads"], cfg["n_groups"]
    state, channels = cfg["ssm_state_size"], cfg["mamba_head_dim"]
    layers = cfg["hybrid_override_pattern"].count("M")
    least_s = max(
        scan_flops(tokens, heads, state, channels, layers)
        / peaks["bf16_flops_per_s"],
        scan_bytes(tokens, groups, heads, state, channels, layers)
        / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (scan_ms * 1e-3)


def setup(run):
    """The probe: how far the first batch's chunks decay, with the run's
    own weights."""
    stats_of = getattr(run.config, "layer_stats", None)
    if stats_of is None or not getattr(run.config, "FIRST", None):
        return
    try:
        from byteps_tpu.parallel.linear_attention import publish_kda_stats
    except ImportError:            # a program without the linear attention
        return
    stats = stats_of(run.cfg, run.rows // run.chips)   # one chip's batch
    if "ssm_stats" in stats:
        run.probes.update(publish_kda_stats(stats["ssm_stats"], GAUGE))


def read(run):
    if run.trace is None:
        return {}
    from benchmark.layers import kda

    ops, programs_ms, steps = kda.capture_ms(run)
    ms = kda.scoped_ms(ops, SCOPES, steps)
    if not ms or not programs_ms:
        return {}
    run.probes["ssm_out_ms"] = ms["out"]
    out = {"ssm.scan_ms": ms["scan"], "ssm.prep_ms": ms["prep"],
           "ssm.proj_ms": ms["proj"],
           "ssm.layer_share_pct": 100.0 * sum(ms.values()) / programs_ms}
    if ms["scan"]:
        import jax

        from benchmark.lib import device

        out["ssm.scan_roofline_pct"] = scan_roofline_pct(
            ms["scan"], run.cfg,
            run.rows // run.chips * run.cfg["seq_len"],
            device.peaks(jax.devices()[0].device_kind))
    return out

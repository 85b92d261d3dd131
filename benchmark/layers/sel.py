"""Layer: selective state-space layers (``byteps_tpu/parallel/
linear_attention.py::selective_scan`` inside ``models/phi4_flash.py::
Mamba1Mixer``: Mamba-1's recurrence, one decay a channel *and* state entry,
a float32 state ``[16, 5120]`` a layer).

From the device trace, first device, line ``XLA Ops``, per traced step
(``layers/kda.py::capture_ms`` reads the capture once for the cell's
readers; its ``scoped_ms`` sums by scope, containers skipped), over every
Mamba-1 layer:

``sel.scan_ms``   ``bps.sel.scan``: the recurrence — the product ``Delta
                  x``, the chunks laid out, the loop over chunks and a
                  chunk's tokens one after another — forward, the chunks
                  recomputed in the backward pass, and backward.
``sel.prep_ms``   ``bps.sel.prep``: the convolution with its bias and SiLU,
                  the x-projection (5120 -> 160 + 16 + 16), the
                  Delta-projection in float32 at the highest precision and
                  its softplus — forward, recomputed, and backward.
``sel.proj_ms``   ``bps.sel.proj``: the in- and the out-projection.
``sel.layer_share_pct``  those three and ``bps.sel.out`` (``D x`` and the
                  ``SiLU(z)`` gate; ``probes.sel_out_ms``) over the time of
                  the capture's programs on ``XLA Modules``.
``sel.scan_roofline_pct``  the least time the chip could take for what the
                  recurrence needs at these shapes, whatever implements it —
                  the larger of ``scan_flops`` over the peak bf16 rate and
                  ``scan_bytes`` over the peak HBM rate (``lib/peaks.json``)
                  — over ``sel.scan_ms``. No chunk length is in either
                  count, so a later kernel or another chunk cannot make them
                  stale, and what a form adds (a state a chunk, the chunked
                  layout's copies) earns nothing.

By hand, one token of one channel and state entry, forward: the decay's
exponent 1 operation, the decayed state plus the write 2, its share of ``C
S`` 2: 5; forward once and backward twice that. A step of 16,384 tokens,
5,120 channels x 16 entries, 2 layers: 3 x 2 x 16,384 x 81,920 x 5 = 40.3
GFLOP, 0.20 ms at the peak bf16 rate — which no elementwise scan reaches:
the exponent and the products run on the vector units, not the MXU. Bytes, a
layer: x, Delta and y [tokens, 5120] and B, C [tokens, 16] in float32, each
read or written once forward and once more backward: 2 x 16,384 x 4 x (3 x
5120 + 2 x 16) = 2.017 GB, 4.03 GB for two layers, 4.93 ms: the scan is
bound by bandwidth on this count. (ISSUE 71: about 40 GFLOP against about 4
GB: the same.)

``bps_sel_min_chunk_log_decay`` (gauge, ``probes`` on the diagnostics line)
comes from a probe before the window: the first batch through the run's own
weights with the ``"sel_stats"`` collection mutable, published by
``parallel/linear_attention.py::publish_kda_stats`` under this gauge's name.

A program without the scopes or the collection reports nothing.
"""

LAYER = "selective state-space layers"
SCOPES = {"scan": "bps.sel.scan", "prep": "bps.sel.prep",
          "proj": "bps.sel.proj", "out": "bps.sel.out"}
GAUGE = "bps_sel_min_chunk_log_decay"
METRICS = {
    "sel.scan_ms": {"unit": "ms", "better": "lower",
                    "source": "device_trace", "moves": "step_ms_p50"},
    "sel.prep_ms": {"unit": "ms", "better": "lower",
                    "source": "device_trace", "moves": "step_ms_p50"},
    "sel.proj_ms": {"unit": "ms", "better": "lower",
                    "source": "device_trace", "moves": "step_ms_p50"},
    "sel.layer_share_pct": {"unit": "%", "better": "lower",
                            "source": "device_trace",
                            "moves": "step_ms_p50"},
    "sel.scan_roofline_pct": {"unit": "%", "better": "higher",
                              "source": "device_trace", "moves": "mfu_pct"},
}


def scan_flops(tokens: int, channels: int, state: int, layers: int) -> int:
    """The recurrence token by token, forward once and backward twice
    that (docstring)."""
    return 3 * layers * tokens * channels * state * 5


def scan_bytes(tokens: int, channels: int, state: int, layers: int) -> int:
    """x, Delta, y a channel and B, C a state entry, float32, forward and
    backward once each."""
    return layers * 2 * tokens * 4 * (3 * channels + 2 * state)


def scan_roofline_pct(scan_ms: float, cfg: dict, tokens: int, layers: int,
                      peaks: dict) -> float:
    """``layers``: the held Mamba-1 layers (the configuration's
    ``layer_counts``)."""
    channels = cfg["mamba_expand"] * cfg["hidden_size"]
    state = cfg["mamba_d_state"]
    least_s = max(
        scan_flops(tokens, channels, state, layers)
        / peaks["bf16_flops_per_s"],
        scan_bytes(tokens, channels, state, layers)
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
    if "sel_stats" in stats:
        run.probes.update(publish_kda_stats(stats["sel_stats"], GAUGE))


def read(run):
    if run.trace is None:
        return {}
    from benchmark.layers import kda

    ops, programs_ms, steps = kda.capture_ms(run)
    ms = kda.scoped_ms(ops, SCOPES, steps)
    if not ms or not programs_ms:
        return {}
    run.probes["sel_out_ms"] = ms["out"]
    out = {"sel.scan_ms": ms["scan"], "sel.prep_ms": ms["prep"],
           "sel.proj_ms": ms["proj"],
           "sel.layer_share_pct": 100.0 * sum(ms.values()) / programs_ms}
    if ms["scan"]:
        import jax

        from benchmark.lib import device

        out["sel.scan_roofline_pct"] = scan_roofline_pct(
            ms["scan"], run.cfg,
            run.rows // run.chips * run.cfg["seq_len"],
            run.config.layer_counts(run.cfg)["mamba"],
            device.peaks(jax.devices()[0].device_kind))
    return out

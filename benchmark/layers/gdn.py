"""Layer: per-head linear attention (``byteps_tpu/parallel/
linear_attention.py::kda_attention`` with one decay a head inside
``models/qwen3_next.py::GatedDeltaNet``: Gated DeltaNet as a chunked scan,
16 key heads under 32 value heads).

From the device trace, first device, line ``XLA Ops``, per traced step
(``layers/kda.py::capture_ms`` reads the capture once for the cell's
readers; its ``scoped_ms`` sums by scope, containers skipped):

``gdn.scan_ms``   ``bps.gdn.scan``: the chunked recurrence — a chunk's
                  masked products, the triangular system's inverse, the
                  scan over chunks — forward, the forward recomputed in the
                  backward pass, and backward.
``gdn.prep_ms``   ``bps.gdn.prep``: the projections' epilogue — the
                  convolution, SiLU, l2 norms, the decay, beta and the
                  cumulated log-decay — the same three ways.
``gdn.layer_share_pct``  those two and ``bps.gdn.out`` (head norm, the
                  ``SiLU(z)`` gate; ``probes.gdn_out_ms``) over the time of
                  the capture's programs on ``XLA Modules``. The layers'
                  projections carry no scope of theirs and are not in it.
``gdn.scan_roofline_pct``  the least time the chip could take for what the
                  recurrence needs at these shapes, whatever implements it
                  — the larger of ``scan_flops`` over the peak bf16 rate and
                  ``scan_bytes`` over the peak HBM rate (``lib/peaks.json``)
                  — over ``gdn.scan_ms``. No chunk length is in either
                  count, so a later kernel or another chunk cannot make them
                  stale, and what a chunked form adds (a chunk's pairs, the
                  triangular solve, a state a chunk) earns nothing.

By hand, one token of one value head, keys d_k, values d_v, forward, 2
operations a multiply-add: the decay of the state d_k d_v, ``S^T k`` 2 d_k
d_v, the rank-one update 2 d_k d_v, ``S^T q`` 2 d_k d_v: 7 d_k d_v = 114,688
at 128 x 128; forward once and backward twice that. A step of 16,384 tokens,
32 value heads, 3 layers: 541.2 GFLOP, 2.75 ms at the peak. Bytes, a layer:
q and k [tokens, 16, 128] (a key head serves two value heads and is read
once), v and o [tokens, 32, 128], g and beta [tokens, 32] — the decay is
one float a head and token, not 128 — in float32, each read or written once
forward and once more backward: 2 x 16,384 x 4 x (2 x 2048 + 2 x 4096 + 64) =
1.619 GB, 4.86 GB for three layers, 5.93 ms: the scan is bound by bandwidth
on this count.

``bps_kda_min_chunk_log_decay`` (gauge, ``probes`` on the diagnostics line)
comes from a probe before the window: the first batch through the run's own
weights with the ``"kda_stats"`` collection mutable, published by
``parallel/linear_attention.py::publish_kda_stats`` (``layers/kda.py::
setup``, called, not copied).

A program without the scopes or the collection reports nothing.
"""

LAYER = "per-head linear attention"
SCOPES = {"scan": "bps.gdn.scan", "prep": "bps.gdn.prep",
          "out": "bps.gdn.out"}
LINEAR = "linear_attention"
METRICS = {
    "gdn.scan_ms": {"unit": "ms", "better": "lower",
                    "source": "device_trace", "moves": "step_ms_p50"},
    "gdn.prep_ms": {"unit": "ms", "better": "lower",
                    "source": "device_trace", "moves": "step_ms_p50"},
    "gdn.layer_share_pct": {"unit": "%", "better": "lower",
                            "source": "device_trace",
                            "moves": "step_ms_p50"},
    "gdn.scan_roofline_pct": {"unit": "%", "better": "higher",
                              "source": "device_trace", "moves": "mfu_pct"},
}


def scan_flops(tokens: int, heads: int, d_k: int, d_v: int,
               layers: int) -> int:
    """The recurrence token by token, forward once and backward twice
    that (docstring)."""
    return 3 * layers * tokens * heads * 7 * d_k * d_v


def scan_bytes(tokens: int, key_heads: int, heads: int, d_k: int, d_v: int,
               layers: int) -> int:
    """q, k at the key heads, v, o at the value heads, g and beta one float
    a value head, float32, forward and backward once each."""
    per_token = 4 * (2 * key_heads * d_k + 2 * heads * d_v + 2 * heads)
    return layers * 2 * tokens * per_token


def linear_layers(cfg: dict) -> int:
    """The Gated DeltaNet layers among the first ``num_hidden_layers``."""
    n, every = cfg["num_hidden_layers"], cfg["full_attention_interval"]
    return n - n // every


def scan_roofline_pct(scan_ms: float, cfg: dict, tokens: int,
                      peaks: dict) -> float:
    heads, key_heads = (cfg["linear_num_value_heads"],
                        cfg["linear_num_key_heads"])
    d_k, d_v = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    layers = linear_layers(cfg)
    least_s = max(
        scan_flops(tokens, heads, d_k, d_v, layers)
        / peaks["bf16_flops_per_s"],
        scan_bytes(tokens, key_heads, heads, d_k, d_v, layers)
        / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (scan_ms * 1e-3)


def setup(run):
    """The probe: how far the first batch's chunks decay, with the run's
    own weights."""
    from benchmark.layers import kda

    kda.setup(run)


def read(run):
    if run.trace is None:
        return {}
    from benchmark.layers import kda

    ops, programs_ms, steps = kda.capture_ms(run)
    ms = kda.scoped_ms(ops, SCOPES, steps)
    if not ms or not programs_ms:
        return {}
    run.probes["gdn_out_ms"] = ms["out"]
    out = {"gdn.scan_ms": ms["scan"], "gdn.prep_ms": ms["prep"],
           "gdn.layer_share_pct": 100.0 * sum(ms.values()) / programs_ms}
    if ms["scan"]:
        import jax

        from benchmark.lib import device

        out["gdn.scan_roofline_pct"] = scan_roofline_pct(
            ms["scan"], run.cfg,
            run.rows // run.chips * run.cfg["seq_len"],
            device.peaks(jax.devices()[0].device_kind))
    return out

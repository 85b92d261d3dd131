"""Layer: windowed and global attention (``models/laguna.py::
LagunaAttention``: grouped-query softmax attention through
``parallel.full_attention(window=...)``, on the chip the flash kernels of
``ops/flash_attention.py`` with the group's key head read by the index maps
and, under a window, grids that walk the band alone).

From the device trace, first device, line ``XLA Ops``, per traced step
(``layers/kda.py::capture_ms`` reads the capture once for the cell's
readers), over every layer of the kind:

``swa.window_ms``  what runs under ``bps.swa.window``, the attention call
                   of the windowed layers: the two kernels
                   (``bps_flash_fwd``, ``bps_flash_bwd``: one backward call
                   since PR 57, where ``bps_flash_dq`` and ``bps_flash_dkv``
                   were two) and the transposes, casts and row sums around
                   them —
                   forward, the forward recomputed in the backward pass,
                   and backward.
``swa.full_ms``    the same under ``bps.swa.full``, the global layers.
``swa.proj_ms``    what runs under ``bps.swa.proj`` in both kinds: the q, k,
                   v and gate projections, the rotation, the gate's sigmoid
                   and product, and ``W_o``.
``swa.layer_share_pct``  those three over the time of the capture's
                   programs on ``XLA Modules``.
``swa.window_roofline_pct`` / ``swa.full_roofline_pct``  the least time the
                   chip could take for exact attention over the band / the
                   causal triangle of the layers of that kind — the larger
                   of ``attend_flops`` over the peak bf16 rate and
                   ``attend_bytes`` over the peak HBM rate
                   (``lib/peaks.json``) — over ``swa.window_ms`` /
                   ``swa.full_ms``.
``swa.walked_pairs_ratio`` (program counters): the (query, key) pairs of the
                   blocks the windowed calls' form computes over the pairs
                   the band holds, ``bps_attention_window_walked_pairs`` /
                   ``bps_attention_window_needed_pairs``, counted at trace
                   time from the shapes and the kernels' blocks (1 would be
                   a form that computes the band and nothing else; the XLA
                   form's square reads s^2 / needed).

By hand: a (query, key) pair of one head costs 2 x 128 (its score) + 2 x 128
(its value) operations forward and twice that backward: 1,536. The band of
a window of 512 over 8,192 rows holds 512 x 8,192 - 130,816 = 4,063,488
pairs, 64 heads, 3 layers: 1.198 TFLOP, 6.08 ms at the peak; the causal
triangle 33,558,528 pairs, 48 heads, 2 layers: 4.948 TFLOP, 25.12 ms. Bytes:
q and o [s, heads, 128], k and v [s, 8, 128] and the four gradients, each
once in bf16: 2 x 2 x 8,192 x (2 x 64 + 16) x 128 = 0.60 GB a windowed
layer, 2.2 ms for three; 0.47 GB a global one, 1.1 ms for two: both kinds
are bound by arithmetic. The recomputed forward earns nothing, and neither
does a block's part outside the band.

A program without the scopes or the counters reports nothing.
"""

LAYER = "windowed and global attention"
SCOPES = {"window": "bps.swa.window", "full": "bps.swa.full",
          "proj": "bps.swa.proj"}
WALKED = "bps_attention_window_walked_pairs"
NEEDED = "bps_attention_window_needed_pairs"
WINDOWED = "sliding_attention"
METRICS = {
    "swa.window_ms": {"unit": "ms", "better": "lower",
                      "source": "device_trace", "moves": "step_ms_p50"},
    "swa.full_ms": {"unit": "ms", "better": "lower",
                    "source": "device_trace", "moves": "step_ms_p50"},
    "swa.proj_ms": {"unit": "ms", "better": "lower",
                    "source": "device_trace", "moves": "step_ms_p50"},
    "swa.layer_share_pct": {"unit": "%", "better": "lower",
                            "source": "device_trace",
                            "moves": "step_ms_p50"},
    "swa.window_roofline_pct": {"unit": "%", "better": "higher",
                                "source": "device_trace",
                                "moves": "mfu_pct"},
    "swa.full_roofline_pct": {"unit": "%", "better": "higher",
                              "source": "device_trace", "moves": "mfu_pct"},
    "swa.walked_pairs_ratio": {"unit": "ratio", "better": "lower",
                               "source": "program_counter",
                               "moves": "tokens_per_s_per_chip"},
}


def needed_pairs(seq_len: int, window=None) -> int:
    """Pairs of one head over one sequence: the causal triangle, or the
    band ``sum_q min(q + 1, window)``."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * seq_len - window * (window - 1) // 2


def attend_flops(rows: int, seq_len: int, heads: int, head_dim: int,
                 window=None) -> int:
    """One layer, forward and backward: 6 x 2 x head_dim a pair."""
    return rows * needed_pairs(seq_len, window) * heads * 12 * head_dim


def attend_bytes(rows: int, seq_len: int, heads: int, kv_heads: int,
                 head_dim: int, operand_bytes: int = 2) -> int:
    """One layer: q, o at ``heads``, k, v at ``kv_heads`` and their
    gradients, each once."""
    return (2 * rows * seq_len * 2 * (heads + kv_heads) * head_dim
            * operand_bytes)


def roofline_pct(ms: float, cfg: dict, rows: int, peaks: dict,
                 windowed: bool) -> float:
    """The layers of one kind among the first ``num_hidden_layers``."""
    n = cfg["num_hidden_layers"]
    heads = [h for kind, h in zip(cfg["layer_types"][:n],
                                  cfg["num_attention_heads_per_layer"][:n])
             if (kind == WINDOWED) == windowed]
    window = cfg["sliding_window"] if windowed else None
    least_s = max(
        sum(attend_flops(rows, cfg["seq_len"], h, cfg["head_dim"], window)
            for h in heads) / peaks["bf16_flops_per_s"],
        sum(attend_bytes(rows, cfg["seq_len"], h,
                         cfg["num_key_value_heads"], cfg["head_dim"])
            for h in heads) / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms * 1e-3)


def walked_pairs_ratio():
    """None where the program has no such counters, or no windowed site."""
    from byteps_tpu.monitor import metrics

    needed = metrics.counter(NEEDED)
    return metrics.counter(WALKED) / needed if needed else None


def read(run):
    out = {"swa.walked_pairs_ratio": walked_pairs_ratio()}
    if run.trace is None:
        return out
    from benchmark.layers import kda

    ops, programs_ms, steps = kda.capture_ms(run)
    ms = kda.scoped_ms(ops, SCOPES, steps)
    if not ms or not programs_ms:
        return out
    out.update({"swa.window_ms": ms["window"], "swa.full_ms": ms["full"],
                "swa.proj_ms": ms["proj"],
                "swa.layer_share_pct": 100.0 * sum(ms.values())
                / programs_ms})
    import jax

    from benchmark.lib import device

    peaks = device.peaks(jax.devices()[0].device_kind)
    for key, windowed in (("window", True), ("full", False)):
        if ms[key]:
            out[f"swa.{key}_roofline_pct"] = roofline_pct(
                ms[key], run.cfg, run.rows // run.chips, peaks, windowed)
    return out

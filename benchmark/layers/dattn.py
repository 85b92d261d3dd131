"""Layer: differential attention (``models/phi4_flash.py::
DifferentialAttention``: attention as a difference of two softmax maps under
a learned lambda, 20 pairs of query heads over 10 pairs of key heads of 64,
a pair's value 128 wide; windowed, full and cross layers; each kind's two
maps are two calls of ``parallel.full_attention`` at 40 / 20 x 64, on the
chip the flash kernels of ``ops/flash_attention.py``).

From the device trace, first device, line ``XLA Ops``, per traced step
(``layers/kda.py::capture_ms`` reads the capture once for the cell's
readers), over every layer of the kind:

``dattn.window_ms``  what runs under ``bps.dattn.window``, the two attention
                     calls of the windowed layers: the kernels
                     (``bps_flash_fwd``, ``bps_flash_bwd``) and the copies,
                     transposes and casts around them — forward, the forward
                     recomputed in the backward pass, and backward.
``dattn.full_ms``    the same under ``bps.dattn.full``, the one layer that
                     reads every key at or before the query and hands its K
                     and V on.
``dattn.cross_ms``   the same under ``bps.dattn.cross``, the layers that
                     read the handed K and V.
``dattn.proj_ms``    ``bps.dattn.proj`` in all three kinds: the q, k, v
                     projections (a cross layer's: q alone) and ``W_o``.
``dattn.diff_ms``    ``bps.dattn.diff``: lambda, the two maps laid side by
                     side, the subtraction, the sub-norm over the pair's 128
                     channels.
``dattn.layer_share_pct``  those five over the time of the capture's
                     programs on ``XLA Modules``.
``dattn.window_roofline_pct``  the least time the chip could take for the
                     band's pairs with each score map computed once —
                     the larger of ``attend_flops`` over the peak bf16 rate
                     and ``layers/swa.py::attend_bytes`` over the peak HBM
                     rate (``lib/peaks.json``) — over ``dattn.window_ms``.
``dattn.full_roofline_pct``  the same for the causal triangle of the full
                     and the cross layers, over ``dattn.full_ms +
                     dattn.cross_ms``.
``dattn.walked_pairs_ratio`` (program counters): ``layers/swa.py::
                     walked_pairs_ratio``: the (query, key) pairs of the
                     blocks the windowed calls' form computes over the pairs
                     the band holds, at trace time.

By hand: a (query, key) pair of one pair of heads costs two scores, 2 x 2 x
64, and two maps over a value of 128, 2 x 2 x 128: 768 operations forward
and twice that backward, 2,304; 20 pairs of heads: 46,080. The band of a
window of 512 over 16,384 rows holds 512 x 16,384 - 130,816 = 8,257,792
pairs (``layers/swa.py::needed_pairs``), one layer: 0.381 TFLOP, 1.93 ms at
the peak; the causal triangle 134,225,920 pairs, two layers: 12.370 TFLOP,
62.79 ms. Bytes, a layer: q [s, 40, 64] and o [s, 20, 128], k and v [s, 20,
64] and the four gradients, each once in bf16: 2 x 2 x 16,384 x (2 x 40 + 2
x 20) x 64 = 0.50 GB, 0.61 ms: both kinds are bound by arithmetic. The
program computes each score map twice (once a value half: module docstring
of ``phi4_flash.py``): that second computation, the recomputed forward and a
block's part outside the band or above the diagonal earn nothing.

A program without the scopes or the counters reports nothing.
"""

LAYER = "differential attention"
SCOPES = {"window": "bps.dattn.window", "full": "bps.dattn.full",
          "cross": "bps.dattn.cross", "proj": "bps.dattn.proj",
          "diff": "bps.dattn.diff"}
_MS = {"unit": "ms", "better": "lower", "source": "device_trace",
       "moves": "step_ms_p50"}
_ROOFLINE = {"unit": "%", "better": "higher", "source": "device_trace",
             "moves": "mfu_pct"}
METRICS = {
    "dattn.window_ms": _MS, "dattn.full_ms": _MS, "dattn.cross_ms": _MS,
    "dattn.proj_ms": _MS, "dattn.diff_ms": _MS,
    "dattn.layer_share_pct": {"unit": "%", "better": "lower",
                              "source": "device_trace",
                              "moves": "step_ms_p50"},
    "dattn.window_roofline_pct": _ROOFLINE,
    "dattn.full_roofline_pct": _ROOFLINE,
    "dattn.walked_pairs_ratio": {"unit": "ratio", "better": "lower",
                                 "source": "program_counter",
                                 "moves": "tokens_per_s_per_chip"},
}


def attend_flops(rows: int, seq_len: int, pairs: int, head_dim: int,
                 window=None) -> int:
    """One layer, forward and backward, each score map once: a pair of a
    pair of heads costs 3 x (2 scores of 2 head_dim + 2 maps over 2 x 2
    head_dim)."""
    from benchmark.layers import swa

    return (rows * swa.needed_pairs(seq_len, window) * pairs
            * 3 * (2 * 2 * head_dim + 2 * 2 * 2 * head_dim))


def roofline_pct(ms: float, cfg: dict, rows: int, peaks: dict, layers: int,
                 window=None) -> float:
    from benchmark.layers import swa

    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    head_dim = cfg["hidden_size"] // heads
    least_s = layers * max(
        attend_flops(rows, cfg["seq_len"], heads // 2, head_dim, window)
        / peaks["bf16_flops_per_s"],
        swa.attend_bytes(rows, cfg["seq_len"], heads, kv_heads, head_dim)
        / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms * 1e-3)


def read(run):
    from benchmark.layers import swa

    out = {"dattn.walked_pairs_ratio": swa.walked_pairs_ratio()}
    if run.trace is None:
        return out
    from benchmark.layers import kda

    ops, programs_ms, steps = kda.capture_ms(run)
    ms = kda.scoped_ms(ops, SCOPES, steps)
    if not ms or not programs_ms:
        return out
    out.update({f"dattn.{key}_ms": ms[key] for key in SCOPES})
    out["dattn.layer_share_pct"] = 100.0 * sum(ms.values()) / programs_ms
    import jax

    from benchmark.lib import device

    peaks = device.peaks(jax.devices()[0].device_kind)
    rows, layers = run.rows // run.chips, run.config.layer_counts(run.cfg)
    if ms["window"]:
        out["dattn.window_roofline_pct"] = roofline_pct(
            ms["window"], run.cfg, rows, peaks, layers["window"],
            run.cfg["sliding_window"])
    if ms["full"] + ms["cross"]:
        out["dattn.full_roofline_pct"] = roofline_pct(
            ms["full"] + ms["cross"], run.cfg, rows, peaks,
            layers["full"] + layers["cross"])
    return out

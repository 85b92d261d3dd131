"""Layer: windowed and global attention, one head count in both kinds
(``models/mellum.py::MellumAttention``: grouped-query softmax attention, 32
query heads over 4 key heads of 128, through
``parallel.full_attention(window=...)``, on the chip the flash kernels of
``ops/flash_attention.py`` with the group's key head read by the index maps
and, under the window of 1024, grids that walk the band alone; no gate).

``layers/swa.py``'s reader under this cell's names — ``swa.*``'s
``workloads`` lists are not this PR's to append to, and its roofline reads
``num_attention_heads_per_layer``, a key this source does not have. The
scopes are the same (``bps.swa.window`` / ``bps.swa.full`` around the
attention call of each kind, ``bps.swa.proj`` around q, k, v, the rotation
and ``W_o``), the counters are the same, and the counting functions
(``needed_pairs``, ``attend_flops``, ``attend_bytes``,
``walked_pairs_ratio``) are ``swa.py``'s, called with this layer's shapes:
nothing of it is copied.

``mswa.window_ms``, ``mswa.full_ms``, ``mswa.proj_ms``,
``mswa.layer_share_pct``: as ``swa.*`` of those names (forward, the forward
recomputed in the backward pass, and backward).
``mswa.window_roofline_pct`` / ``mswa.full_roofline_pct``: the least time
the chip could take for exact attention over the band / the causal
triangle of the layers of that kind over ``mswa.window_ms`` /
``mswa.full_ms``.
``mswa.walked_pairs_ratio``: ``bps_attention_window_walked_pairs`` /
``bps_attention_window_needed_pairs``, at trace time.

By hand, a sequence: a pair of one head costs 1,536 operations forward and
backward; the band of a window of 1024 over 8,192 rows holds 1024 x 8,192 -
523,776 = 7,864,832 pairs, 32 heads, 3 layers: 1.160 TFLOP, 5.89 ms at the
peak; the causal triangle 33,558,528 pairs, 32 heads, 1 layer: 1.649 TFLOP,
8.37 ms. Bytes: q and o [s, 32, 128], k and v [s, 4, 128] and the four
gradients, each once in bf16: 2 x 2 x 8,192 x (2 x 32 + 2 x 4) x 128 = 0.30
GB a layer, 0.37 ms: both kinds are bound by arithmetic. Times the rows of
the batch.

A program without the scopes or the counters reports nothing.
"""

LAYER = "windowed and global attention"
WINDOWED = "sliding_attention"
METRICS = {
    "mswa.window_ms": {"unit": "ms", "better": "lower",
                       "source": "device_trace", "moves": "step_ms_p50"},
    "mswa.full_ms": {"unit": "ms", "better": "lower",
                     "source": "device_trace", "moves": "step_ms_p50"},
    "mswa.proj_ms": {"unit": "ms", "better": "lower",
                     "source": "device_trace", "moves": "step_ms_p50"},
    "mswa.layer_share_pct": {"unit": "%", "better": "lower",
                             "source": "device_trace",
                             "moves": "step_ms_p50"},
    "mswa.window_roofline_pct": {"unit": "%", "better": "higher",
                                 "source": "device_trace",
                                 "moves": "mfu_pct"},
    "mswa.full_roofline_pct": {"unit": "%", "better": "higher",
                               "source": "device_trace", "moves": "mfu_pct"},
    "mswa.walked_pairs_ratio": {"unit": "ratio", "better": "lower",
                                "source": "program_counter",
                                "moves": "tokens_per_s_per_chip"},
}


def roofline_pct(ms: float, cfg: dict, rows: int, peaks: dict,
                 windowed: bool) -> float:
    """The layers of one kind among the first ``num_hidden_layers``."""
    from benchmark.layers import swa

    layers = sum((kind == WINDOWED) == windowed for kind in
                 cfg["layer_types"][:cfg["num_hidden_layers"]])
    heads, head_dim = cfg["num_attention_heads"], cfg["head_dim"]
    least_s = layers * max(
        swa.attend_flops(rows, cfg["seq_len"], heads, head_dim,
                         cfg["sliding_window"] if windowed else None)
        / peaks["bf16_flops_per_s"],
        swa.attend_bytes(rows, cfg["seq_len"], heads,
                         cfg["num_key_value_heads"], head_dim)
        / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms * 1e-3)


def read(run):
    from benchmark.layers import swa

    out = {"mswa.walked_pairs_ratio": swa.walked_pairs_ratio()}
    if run.trace is None:
        return out
    from benchmark.layers import kda

    ops, programs_ms, steps = kda.capture_ms(run)
    ms = kda.scoped_ms(ops, swa.SCOPES, steps)
    if not ms or not programs_ms:
        return out
    out.update({"mswa.window_ms": ms["window"], "mswa.full_ms": ms["full"],
                "mswa.proj_ms": ms["proj"],
                "mswa.layer_share_pct": 100.0 * sum(ms.values())
                / programs_ms})
    import jax

    from benchmark.lib import device

    peaks = device.peaks(jax.devices()[0].device_kind)
    for key, windowed in (("window", True), ("full", False)):
        if ms[key]:
            out[f"mswa.{key}_roofline_pct"] = roofline_pct(
                ms[key], run.cfg, run.rows // run.chips, peaks, windowed)
    return out

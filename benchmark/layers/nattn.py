"""Layer: position-free attention (``models/nemotron_h.py::
NemotronAttention``: grouped-query causal softmax attention 128 wide with no
rotation and no q/k norm through ``parallel.full_attention``, on the chip
the grouped flash kernels of ``ops/flash_attention.py`` at 16 query heads a
key head).

From the device trace, first device, line ``XLA Ops``, per traced step
(``layers/kda.py::capture_ms`` reads the capture once for the cell's
readers), over every attention layer:

``nattn.attend_ms``  what runs under ``bps.nattn.attend``, the attention
                     call: the kernels (``bps_flash_fwd``, ``bps_flash_bwd``)
                     and the transposes, casts and row sums around them —
                     forward, the forward recomputed in the backward pass,
                     and backward.
``nattn.proj_ms``    what runs under ``bps.nattn.proj``: the q, k and v
                     projections and ``W_o``.
``nattn.attend_roofline_pct``  the least time the chip could take for exact
                     attention over the causal triangle — the larger of
                     ``attend_flops`` over the peak bf16 rate and
                     ``attend_bytes`` over the peak HBM rate
                     (``lib/peaks.json``; the functions are
                     ``layers/swa.py``'s, called with this layer's shapes) —
                     over ``nattn.attend_ms``.

By hand: a (query, key) pair of one head costs 2 x 128 (its score) + 2 x 128
(its value) operations forward and twice that backward: 1,536. The causal
triangle over 16,384 rows holds 134,225,920 pairs, 32 heads, one layer: 6.597
TFLOP, 33.49 ms at the peak. Bytes: q and o [s, 32, 128], k and v [s, 2, 128]
and the four gradients, each once in bf16: 2 x 2 x 16,384 x (2 x 32 + 2 x 2)
x 128 = 0.57 GB, 0.70 ms: bound by arithmetic. The recomputed forward earns
nothing, and neither does a block's part above the diagonal.

A program without the scopes reports nothing.
"""

LAYER = "position-free attention"
SCOPES = {"attend": "bps.nattn.attend", "proj": "bps.nattn.proj"}
METRICS = {
    "nattn.attend_ms": {"unit": "ms", "better": "lower",
                        "source": "device_trace", "moves": "step_ms_p50"},
    "nattn.proj_ms": {"unit": "ms", "better": "lower",
                      "source": "device_trace", "moves": "step_ms_p50"},
    "nattn.attend_roofline_pct": {"unit": "%", "better": "higher",
                                  "source": "device_trace",
                                  "moves": "mfu_pct"},
}


def attend_roofline_pct(ms: float, cfg: dict, rows: int,
                        peaks: dict) -> float:
    """The attention layers among the pattern's."""
    from benchmark.layers import swa

    layers = cfg["hybrid_override_pattern"].count("*")
    heads, head_dim = cfg["num_attention_heads"], cfg["head_dim"]
    least_s = layers * max(
        swa.attend_flops(rows, cfg["seq_len"], heads, head_dim)
        / peaks["bf16_flops_per_s"],
        swa.attend_bytes(rows, cfg["seq_len"], heads,
                         cfg["num_key_value_heads"], head_dim)
        / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms * 1e-3)


def read(run):
    if run.trace is None:
        return {}
    from benchmark.layers import kda

    ops, programs_ms, steps = kda.capture_ms(run)
    ms = kda.scoped_ms(ops, SCOPES, steps)
    if not ms or not programs_ms:
        return {}
    out = {"nattn.attend_ms": ms["attend"], "nattn.proj_ms": ms["proj"]}
    if ms["attend"]:
        import jax

        from benchmark.lib import device

        out["nattn.attend_roofline_pct"] = attend_roofline_pct(
            ms["attend"], run.cfg, run.rows // run.chips,
            device.peaks(jax.devices()[0].device_kind))
    return out

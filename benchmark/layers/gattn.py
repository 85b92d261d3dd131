"""Layer: gated attention (``models/qwen3_next.py::GatedAttention``:
grouped-query causal softmax attention 256 wide through
``parallel.full_attention``, on the chip the flash kernels of
``ops/flash_attention.py`` at their fourth width, under a per-channel
sigmoid gate on the output).

From the device trace, first device, line ``XLA Ops``, per traced step
(``layers/kda.py::capture_ms`` reads the capture once for the cell's
readers), over every gated attention layer:

``gattn.attend_ms``  what runs under ``bps.gattn.attend``, the attention
                     call: the two kernels (``bps_flash_fwd``,
                     ``bps_flash_bwd``: one backward call since PR 57,
                     where ``bps_flash_dq`` and ``bps_flash_dkv`` were
                     two) and the transposes, casts and row sums around
                     them — forward,
                     the forward recomputed in the backward pass, and
                     backward.
``gattn.proj_ms``    what runs under ``bps.gattn.proj``: the q (with its
                     gate), k and v projections, the q and k norms, the
                     rotation, the gate's sigmoid and product, and ``W_o``.
``gattn.attend_roofline_pct``  the least time the chip could take for exact
                     attention over the causal triangle — the larger of
                     ``attend_flops`` over the peak bf16 rate and
                     ``attend_bytes`` over the peak HBM rate
                     (``lib/peaks.json``; the functions are
                     ``layers/swa.py``'s, called with this layer's shapes) —
                     over ``gattn.attend_ms``.

By hand: a (query, key) pair of one head costs 2 x 256 (its score) + 2 x 256
(its value) operations forward and twice that backward: 3,072. The causal
triangle over 16,384 rows holds 134,225,920 pairs, 16 heads, one layer: 6.597
TFLOP, 33.49 ms at the peak. Bytes: q and o [s, 16, 256], k and v [s, 2, 256]
and the four gradients, each once in bf16: 2 x 2 x 16,384 x (2 x 16 + 2 x 2)
x 256 = 0.60 GB, 0.74 ms: bound by arithmetic. The recomputed forward earns
nothing, and neither does a block's part above the diagonal.

A program without the scopes reports nothing.
"""

LAYER = "gated attention"
SCOPES = {"attend": "bps.gattn.attend", "proj": "bps.gattn.proj"}
METRICS = {
    "gattn.attend_ms": {"unit": "ms", "better": "lower",
                        "source": "device_trace", "moves": "step_ms_p50"},
    "gattn.proj_ms": {"unit": "ms", "better": "lower",
                      "source": "device_trace", "moves": "step_ms_p50"},
    "gattn.attend_roofline_pct": {"unit": "%", "better": "higher",
                                  "source": "device_trace",
                                  "moves": "mfu_pct"},
}


def attend_roofline_pct(ms: float, cfg: dict, rows: int,
                        peaks: dict) -> float:
    """The gated attention layers among the first ``num_hidden_layers``."""
    from benchmark.layers import swa

    layers = cfg["num_hidden_layers"] // cfg["full_attention_interval"]
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
    out = {"gattn.attend_ms": ms["attend"], "gattn.proj_ms": ms["proj"]}
    if ms["attend"]:
        import jax

        from benchmark.lib import device

        out["gattn.attend_roofline_pct"] = attend_roofline_pct(
            ms["attend"], run.cfg, run.rows // run.chips,
            device.peaks(jax.devices()[0].device_kind))
    return out

"""Layer: latent attention (``models/kimi_linear.py::KimiLatentAttention``:
``parallel.full_attention`` over keys wider than values, on the chip the
flash kernels of ``ops/flash_attention.py`` at two widths).

From the device trace, first device, line ``XLA Ops``, per traced step
(``layers/kda.py::capture_ms`` reads the capture once for the cell's three
readers):

``mla.attend_ms``  what runs under the scope ``bps.mla.attend``: the two
                   kernels (``bps_flash_fwd``, ``bps_flash_bwd``: one
                   backward call since PR 57, where ``bps_flash_dq`` and
                   ``bps_flash_dkv`` were two) and the transposes, casts and
                   row sums around them — forward, the forward recomputed in the
                   backward pass, and backward.
``mla.attend_roofline_pct``  the least time the chip could take for exact
                   causal attention — the larger of ``attend_flops`` over
                   the peak bf16 rate and ``attend_bytes`` over the peak HBM
                   rate (``lib/peaks.json``) — over ``mla.attend_ms``.

By hand: a causal (query, key) pair of one head costs 2 x 192 (its score)
+ 2 x 128 (its value) operations forward and twice that backward (dQ and
dK from the score's gradient, dV and dP from the value product): 6 x (192 +
128) = 1,920. One sequence of 16,384 has 134,225,920 pairs, 32 heads: 8.25
TFLOP, 41.9 ms at the peak. The widths counted are 192 and 128: lanes a
kernel pads a 192-wide operand to show as lost share. Bytes: q, k [s, 32,
192] and v, o [s, 32, 128] and the four gradients, each once in bf16: 2 x 2
x 16,384 x 32 x 640 = 1.34 GB, 1.6 ms: the layer is bound by arithmetic.
The cell runs s 8,192: 33,558,528 pairs, 2.06 TFLOP, 10.5 ms at the peak.
The recomputed forward earns nothing.

A program without the scope reports nothing.
"""

LAYER = "latent attention"
SCOPE = "bps.mla.attend"
METRICS = {
    "mla.attend_ms": {"unit": "ms", "better": "lower",
                      "source": "device_trace", "moves": "step_ms_p50"},
    "mla.attend_roofline_pct": {"unit": "%", "better": "higher",
                                "source": "device_trace",
                                "moves": "mfu_pct"},
}


def attend_flops(rows: int, seq_len: int, heads: int, qk_dim: int,
                 v_dim: int, layers: int) -> int:
    pairs = seq_len * (seq_len + 1) // 2
    return layers * rows * pairs * heads * 6 * (qk_dim + v_dim)


def attend_bytes(rows: int, seq_len: int, heads: int, qk_dim: int,
                 v_dim: int, layers: int, operand_bytes: int = 2) -> int:
    """q, k, v, o and their gradients, each once."""
    return (layers * 2 * rows * seq_len * heads * 2 * (qk_dim + v_dim)
            * operand_bytes)


def attend_roofline_pct(attend_ms: float, cfg: dict, rows: int,
                        peaks: dict) -> float:
    layers = sum(1 for i in cfg["linear_attn_config"]["full_attn_layers"]
                 if i <= cfg["num_hidden_layers"])
    args = (rows, cfg["seq_len"], cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], layers)
    least_s = max(attend_flops(*args) / peaks["bf16_flops_per_s"],
                  attend_bytes(*args) / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (attend_ms * 1e-3)


def read(run):
    if run.trace is None:
        return {}
    from benchmark.layers import kda

    ops, programs_ms, steps = kda.capture_ms(run)
    ms = kda.scoped_ms(ops, {"attend": SCOPE}, steps)
    if not ms or not programs_ms:
        return {}
    import jax

    from benchmark.lib import device

    run.probes["mla_attend_share_pct"] = 100.0 * ms["attend"] / programs_ms
    return {"mla.attend_ms": ms["attend"],
            "mla.attend_roofline_pct": attend_roofline_pct(
                ms["attend"], run.cfg, run.rows // run.chips,
                device.peaks(jax.devices()[0].device_kind))}

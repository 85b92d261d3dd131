"""Layer: rotary latent attention (``models/kimi_linear.py::
KimiLatentAttention`` with a query rank and a rotary key part, every mixer
of ``models/joyai.py``: ``parallel.full_attention`` over keys wider than
values, on the chip the flash kernels of ``ops/flash_attention.py`` at two
widths).

From the device trace, first device, line ``XLA Ops``, per traced step
(``layers/kda.py::capture_ms`` reads the capture once for the cell's three
readers), over every layer that has the scopes — the main stack's and the
MTP module's, whose ops are also in ``mtp.module_ms``: no reader adds
shares across prefixes.

``qmla.attend_ms``  what runs under ``bps.mla.attend``: the two kernels
                    (``bps_flash_fwd``, ``bps_flash_bwd``: one backward
                    call since PR 57, where ``bps_flash_dq`` and
                    ``bps_flash_dkv`` were two) and the transposes, casts
                    and row sums around them —
                    forward, the forward recomputed in the backward pass,
                    and backward.
``qmla.attend_roofline_pct``  the least time the chip could take for exact
                    causal attention over the layers — the larger of
                    ``attend_flops`` over the peak bf16 rate and
                    ``attend_bytes`` over the peak HBM rate
                    (``lib/peaks.json``; both functions are
                    ``layers/mla.py``'s) — over ``qmla.attend_ms``.
``qmla.proj_ms``    what runs under ``bps.mla.proj``: the five projections
                    (q_a, q_b, kv_a, kv_b, o), the two latent norms, the
                    rotation and the concatenations that build q and k.
``qmla.layer_share_pct``  those two over the time of the capture's programs
                    on ``XLA Modules``.

By hand: a causal (query, key) pair of one head costs 6 x (192 + 128) =
1,920 operations, forward and backward. The main stack's layers see s rows,
s (s + 1) / 2 pairs a sequence; the module's layer the s - 2 rows with a
target, (s - 2)(s - 1) / 2 (the program runs it at s rows: the two padded
rows earn nothing). At s 8,192, 32 heads, 5 + 1 layers: 5 x 33,558,528 +
33,542,145 = 201,334,785 pairs, 12.37 TFLOP, 62.8 ms at the peak. Bytes: q,
k [s, 32, 192] and v, o [s, 32, 128] and the four gradients, each once in
bf16, 2 x 2 x 8,192 x 32 x 640 = 0.67 GB a layer, 4.9 ms for six: bound by
arithmetic. The recomputed forward earns nothing.

A program without the scopes reports nothing.
"""

LAYER = "rotary latent attention"
SCOPES = {"attend": "bps.mla.attend", "proj": "bps.mla.proj"}
METRICS = {
    "qmla.attend_ms": {"unit": "ms", "better": "lower",
                       "source": "device_trace", "moves": "step_ms_p50"},
    "qmla.attend_roofline_pct": {"unit": "%", "better": "higher",
                                 "source": "device_trace",
                                 "moves": "mfu_pct"},
    "qmla.proj_ms": {"unit": "ms", "better": "lower",
                     "source": "device_trace", "moves": "step_ms_p50"},
    "qmla.layer_share_pct": {"unit": "%", "better": "lower",
                             "source": "device_trace",
                             "moves": "step_ms_p50"},
}


def attend_roofline_pct(attend_ms: float, cfg: dict, rows: int,
                        peaks: dict) -> float:
    from benchmark.layers import mla

    s = cfg["seq_len"]
    widths = (cfg["num_attention_heads"],
              cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
              cfg["v_head_dim"])
    # (rows of a sequence, layers): the main stack, then the MTP module's
    streams = ((s, cfg["num_hidden_layers"]),
               (s - 2, cfg["num_nextn_predict_layers"]))
    least_s = max(
        sum(mla.attend_flops(rows, n, *widths, layers)
            for n, layers in streams) / peaks["bf16_flops_per_s"],
        sum(mla.attend_bytes(rows, n, *widths, layers)
            for n, layers in streams) / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (attend_ms * 1e-3)


def read(run):
    if run.trace is None:
        return {}
    from benchmark.layers import kda

    ops, programs_ms, steps = kda.capture_ms(run)
    ms = kda.scoped_ms(ops, SCOPES, steps)
    if not ms or not programs_ms:
        return {}
    out = {"qmla.attend_ms": ms["attend"], "qmla.proj_ms": ms["proj"],
           "qmla.layer_share_pct": 100.0 * sum(ms.values()) / programs_ms}
    if ms["attend"]:
        import jax

        from benchmark.lib import device

        out["qmla.attend_roofline_pct"] = attend_roofline_pct(
            ms["attend"], run.cfg, run.rows // run.chips,
            device.peaks(jax.devices()[0].device_kind))
    return out

"""Layer: compressed convolutional attention (``models/zaya.py::
CompressedConvAttention``: grouped-query causal softmax attention computed
inside a latent — 8 query heads over 2 key heads of 128, nothing expanded to
the model's width — through ``parallel.full_attention``, on the chip the
flash kernels of ``ops/flash_attention.py`` 128 wide with 4 query heads a
key head, behind two causal convolutions, a q-k mean, a value shift and
normalised keys under a temperature).

From the device trace, first device, line ``XLA Ops``, per traced step
(``layers/kda.py::capture_ms`` reads the capture once for the cell's
readers), over every layer:

``cca.attend_ms``  what runs under ``bps.cca.attend``, the attention call:
                   the two kernels (``bps_flash_fwd``, ``bps_flash_bwd``:
                   one backward call since PR 57, where ``bps_flash_dq``
                   and ``bps_flash_dkv`` were two) and the transposes, casts
                   and row sums around them — forward, the forward recomputed in the
                   backward pass, and backward.
``cca.mix_ms``     what runs under ``bps.cca.mix``: the depthwise and the
                   grouped convolution, the q-k mean, the value shift, the
                   normalisation, the temperature and the rotation, all in
                   XLA (no kernel of this repo under it: no
                   ``cca.mix_roofline_pct``).
``cca.proj_ms``    what runs under ``bps.cca.proj``: ``W_q``, ``W_k``,
                   ``W_v1``, ``W_v2`` and ``W_o``.
``cca.layer_share_pct``  the three over the capture's program time.
``cca.attend_roofline_pct``  the least time the chip could take for exact
                   attention over the causal triangle in the latent — the
                   larger of ``attend_flops`` over the peak bf16 rate and
                   ``attend_bytes`` over the peak HBM rate
                   (``lib/peaks.json``; the functions are
                   ``layers/swa.py``'s, called with this layer's shapes as
                   ``layers/gattn.py`` calls them) — over ``cca.attend_ms``.

By hand: a (query, key) pair of one head costs 2 x 128 (its score) + 2 x 128
(its value) operations forward and twice that backward: 1,536. The causal
triangle over 16,384 rows holds 134,225,920 pairs, 8 heads, five layers:
8.247 TFLOP, 41.86 ms at the peak. Bytes: q and o [s, 8, 128], k and v [s,
2, 128] and the four gradients, each once in bf16, five layers: 5 x 2 x 2 x
16,384 x (2 x 8 + 2 x 2) x 128 = 0.84 GB, 1.02 ms: bound by arithmetic. The
recomputed forward earns nothing, and neither does a block's part above the
diagonal.

A program without the scopes reports nothing.
"""

LAYER = "compressed convolutional attention"
SCOPES = {"attend": "bps.cca.attend", "mix": "bps.cca.mix",
          "proj": "bps.cca.proj"}
METRICS = {
    "cca.attend_ms": {"unit": "ms", "better": "lower",
                      "source": "device_trace", "moves": "step_ms_p50"},
    "cca.mix_ms": {"unit": "ms", "better": "lower",
                   "source": "device_trace", "moves": "step_ms_p50"},
    "cca.proj_ms": {"unit": "ms", "better": "lower",
                    "source": "device_trace", "moves": "step_ms_p50"},
    "cca.layer_share_pct": {"unit": "%", "better": "lower",
                            "source": "device_trace",
                            "moves": "step_ms_p50"},
    "cca.attend_roofline_pct": {"unit": "%", "better": "higher",
                                "source": "device_trace",
                                "moves": "mfu_pct"},
}


def attend_roofline_pct(ms: float, cfg: dict, rows: int,
                        peaks: dict) -> float:
    """Every one of the ``num_hidden_layers`` layers has the mixer."""
    from benchmark.layers import swa

    heads, head_dim = cfg["num_attention_heads"], cfg["head_dim"]
    least_s = cfg["num_hidden_layers"] * max(
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
    out = {"cca.attend_ms": ms["attend"], "cca.mix_ms": ms["mix"],
           "cca.proj_ms": ms["proj"],
           "cca.layer_share_pct": 100.0 * sum(ms.values()) / programs_ms}
    if ms["attend"]:
        import jax

        from benchmark.lib import device

        out["cca.attend_roofline_pct"] = attend_roofline_pct(
            ms["attend"], run.cfg, run.rows // run.chips,
            device.peaks(jax.devices()[0].device_kind))
    return out

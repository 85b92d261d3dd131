"""Layer: linear attention (``byteps_tpu/parallel/linear_attention.py::
kda_attention`` inside ``models/kimi_linear.py``: the gated delta rule with
a per-channel decay as a chunked scan).

From the device trace, first device, line ``XLA Ops``, per traced step, by
the program's ``jax.named_scope``s in the ``tf_op`` stat of an event's
metadata (``layers/moe.py::scoped_ops`` reads it; nothing of that file is
copied):

``kda.scan_ms``   ``bps.kda.scan``: the chunked recurrence — the decayed
                  products inside a chunk, the triangular system's inverse,
                  the scan over chunks — forward, the forward recomputed in
                  the backward pass, and backward.
``kda.prep_ms``   ``bps.kda.prep``: the projections' epilogue —
                  convolutions, SiLU, l2norm, the decay, beta and the
                  cumulated log-decay — the same three ways.
``kda.layer_share_pct``  those two and ``bps.kda.out`` (head norm, output
                  gate; ``probes.kda_out_ms``) over the time of the
                  capture's programs on ``XLA Modules``. The layers'
                  projections carry no scope of theirs and are not in it.
``kda.scan_roofline_pct``  the least time the chip could take for the
                  chunked algorithm at the file's chunk length — the larger
                  of ``scan_flops`` over the peak bf16 rate and
                  ``scan_bytes`` over the peak HBM rate (``lib/peaks.json``)
                  — over ``kda.scan_ms``. Forward once and backward twice
                  that: the recomputed forward earns nothing.

Where the scan runs as an XLA loop it is a ``%while`` on that line, an event
as long as all the ops of its body, which are events of their own and carry
the scope it was opened under: a container is skipped by name, so nothing is
counted twice. The chip has not run that form since PR 54 — the scan over a
layer's chunks is one kernel pair there (``bps_kda_recurrence_fwd`` /
``_bwd``) and the capture holds no ``%while`` under ``bps.kda.scan`` — but
other scopes' loops are on the line, and the recorded list of PR 39 under
``tests/benchmark/data`` still has the scan's.

By hand, one chunk of C tokens of one head, keys d_k, values d_v, forward,
2 operations a multiply-add, a triangle counted as half its square:
``P(k, k)`` and ``P(q, k)`` C^2 d_k each; the unit-triangular system
solved for d_k + d_v right-hand sides C^2 (d_k + d_v); ``W S``, ``Q S`` and
``K^T U`` 2 C d_k d_v each; ``A_q U`` C^2 d_v: C^2 (3 d_k + 2 d_v) + 6 C
d_k d_v. At C 64 and 128 x 128: 2,621,440 + 6,291,456 = 8,912,896, 139,264
a token (the recurrence token by token needs 114,688). A step of 16,384
tokens, 32 heads, 4 layers, x 3: 876 GFLOP, 4.4 ms at the peak. Bytes, a
layer: q, k, v, g and o [tokens, heads, 128] and beta [tokens, heads] in
float32, each read or written once forward, and once more backward (a
gradient written for a tensor read, read for one written): 2 x (5 x 268.4
+ 2.1) MB; a chunk's state [heads, 128, 128] float32 written forward and
read backward: 2 x 256 x 2.1 MB; 3.78 GB a layer, 4.6 ms, 18.4 ms for 4
layers. The scan is bound by bandwidth on this count: the roofline share is
18.4 ms over ``kda.scan_ms`` there. The cell runs 8,192 tokens in chunks of
32: 3,801,088 a chunk, 374 GFLOP (1.9 ms) and 9.67 GB (11.8 ms) a step.

``bps_kda_min_chunk_log_decay`` (gauge, ``probes`` on the diagnostics line)
comes from a probe before the window: the first batch through the run's own
weights with the ``"kda_stats"`` collection mutable, published by
``parallel/linear_attention.py::publish_kda_stats``.

``capture_ms`` reads the capture once a process for this reader and every
other that asks (``layers/mla.py``, ``layers/eshare.py`` and the rest).

A program without the scopes or the collection reports nothing.
"""

import os

LAYER = "linear attention"
SCOPES = {"scan": "bps.kda.scan", "prep": "bps.kda.prep",
          "out": "bps.kda.out"}
CONTAINERS = ("%while", "%conditional", "%call")
METRICS = {
    "kda.scan_ms": {"unit": "ms", "better": "lower",
                    "source": "device_trace", "moves": "step_ms_p50"},
    "kda.prep_ms": {"unit": "ms", "better": "lower",
                    "source": "device_trace", "moves": "step_ms_p50"},
    "kda.layer_share_pct": {"unit": "%", "better": "lower",
                            "source": "device_trace",
                            "moves": "step_ms_p50"},
    "kda.scan_roofline_pct": {"unit": "%", "better": "higher",
                              "source": "device_trace", "moves": "mfu_pct"},
}
_CAPTURES = {}


# --------------------------------------------------------------------------
# What the chunked scans of one step need, from shapes alone.

def scan_flops(tokens: int, heads: int, d_k: int, d_v: int, chunk: int,
               layers: int) -> int:
    """Forward once and backward twice that (docstring)."""
    per_chunk = (chunk * chunk * (3 * d_k + 2 * d_v)
                 + 6 * chunk * d_k * d_v)
    return 3 * layers * heads * -(-tokens // chunk) * per_chunk


def scan_bytes(tokens: int, heads: int, d_k: int, d_v: int, chunk: int,
               layers: int) -> int:
    """q, k, g at d_k, v, o at d_v, beta, float32, forward and backward
    once each; one float32 state a chunk written and read (docstring)."""
    per_token = 4 * heads * (3 * d_k + 2 * d_v + 1)
    states = 4 * heads * d_k * d_v * -(-tokens // chunk)
    return layers * 2 * (tokens * per_token + states)


def scan_roofline_pct(scan_ms: float, cfg: dict, tokens: int,
                      peaks: dict) -> float:
    linear = cfg["linear_attn_config"]
    layers = sum(1 for i in linear["kda_layers"]
                 if i <= cfg["num_hidden_layers"])
    args = (tokens, linear["num_heads"], linear["head_dim"],
            linear["head_dim"], cfg["kda_chunk"], layers)
    least_s = max(scan_flops(*args) / peaks["bf16_flops_per_s"],
                  scan_bytes(*args) / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (scan_ms * 1e-3)


# --------------------------------------------------------------------------

def scoped_ms(ops, scopes: dict, steps: int) -> dict:
    """Per step, ms under each of ``scopes`` ({key: scope}; an op goes to
    the first that its ``tf_op`` holds), containers skipped. Empty where
    none shows."""
    sums = dict.fromkeys(scopes, 0)
    for name, tf_op, duration_ps in ops:
        if name.startswith(CONTAINERS):
            continue
        for key, scope in scopes.items():
            if scope in tf_op:
                sums[key] += duration_ps
                break
    if not steps or not any(sums.values()):
        return {}
    return {k: v * 1e-9 / steps for k, v in sums.items()}


def capture_ms(run):
    """``(ops, programs_ms, steps)`` of the run's capture: the scoped ops of
    the op line and the summed time a step of the capture's programs on
    ``XLA Modules``; read once for all the readers of a cell."""
    from benchmark.layers import moe
    from benchmark.lib import trace_reduce

    xplane = trace_reduce.find_xplane(os.path.join(run.out_dir, "trace"))
    if xplane not in _CAPTURES:
        steps = run.trace["steps"]
        _CAPTURES[xplane] = (
            moe.scoped_ops(xplane, run.layout),
            sum(d for _, _, d in moe.scoped_ops(
                xplane, run.layout, run.layout.module_line)) * 1e-9 / steps,
            steps)
    return _CAPTURES[xplane]


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
    if "kda_stats" in stats:
        run.probes.update(publish_kda_stats(stats["kda_stats"]))


def read(run):
    if run.trace is None:
        return {}
    # the package's copy of this module holds the capture for all three
    from benchmark.layers import kda as shared

    ops, programs_ms, steps = shared.capture_ms(run)
    ms = scoped_ms(ops, SCOPES, steps)
    if not ms or not programs_ms:
        return {}
    run.probes["kda_out_ms"] = ms["out"]
    out = {"kda.scan_ms": ms["scan"], "kda.prep_ms": ms["prep"],
           "kda.layer_share_pct": 100.0 * sum(ms.values()) / programs_ms}
    if ms["scan"]:
        import jax

        from benchmark.lib import device

        out["kda.scan_roofline_pct"] = scan_roofline_pct(
            ms["scan"], run.cfg,
            run.rows // run.chips * run.cfg["seq_len"],
            device.peaks(jax.devices()[0].device_kind))
    return out


if __name__ == "__main__":
    # python3 benchmark/layers/kda.py <trace_dir> <steps> [out.json.gz]
    # prints the sums under this cell's scopes for a capture a traced run
    # left behind; with a third argument it also writes the capture's
    # scoped ops for tests/benchmark/data: [name cut to 48, tf_op, summed
    # ps, events] per distinct (name, tf_op), and the programs' summed ps.
    import gzip
    import json
    import sys

    sys.path.insert(0, __file__.rsplit("/benchmark/", 1)[0])
    from benchmark.layers import moe
    from benchmark.lib import trace_reduce

    xplane = trace_reduce.find_xplane(sys.argv[1])
    ops = moe.scoped_ops(xplane, trace_reduce.TPU)
    print(json.dumps(scoped_ms(ops, {
        **SCOPES, "mla_attend": "bps.mla.attend",
        "moe_shared": "bps.moe.shared", "moe_route": "bps.moe.route",
        "moe_experts": "bps.moe.experts", "head": "bps.lm.head"},
        int(sys.argv[2]))))
    if len(sys.argv) > 3:
        summed = {}
        for name, tf_op, duration_ps in ops:
            row = summed.setdefault((name[:48], tf_op), [0, 0])
            row[0] += duration_ps
            row[1] += 1
        programs_ps = sum(d for _, _, d in moe.scoped_ops(
            xplane, trace_reduce.TPU, trace_reduce.TPU.module_line))
        with gzip.open(sys.argv[3], "wt") as f:
            json.dump({"steps": int(sys.argv[2]), "programs_ps": programs_ps,
                       "ops": [[n, t, d, c] for (n, t), (d, c)
                               in sorted(summed.items())]}, f)

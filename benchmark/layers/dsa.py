"""Layer: sparse attention (``byteps_tpu/parallel/sparse_attention.py``
inside ``models/keye.py``).

From the device trace, first device, line ``XLA Ops``, per traced step, by
the program's ``jax.named_scope``s in the ``tf_op`` stat of an event's
metadata (``layers/moe.py::scoped_ops`` reads it; nothing of that file is
copied):

``dsa.indexer_ms``   ``bps.dsa.indexer``: the indexer's projections, the
                     index scores, the KL term and their gradients.
``dsa.select_ms``    ``bps.dsa.select``: each query's ``topk``-th score
                     (since PR 34 16 compare-and-count passes over the
                     scores' ordered bits, no ``lax.top_k``, forward only:
                     the backward pass rebuilds the mask from the saved
                     threshold) and the mask.
``dsa.attend_ms``    ``bps.dsa.attend``: attention scores, softmax, values
                     and their gradients, recomputation included. On the
                     chip, since PR 42, the kernels of ``ops/
                     sparse_flash.py`` (``bps_dsa_fwd``, ``bps_dsa_probs``,
                     ``bps_dsa_bwd``: a [block, tile] score in VMEM, the
                     selection an int8 operand, the forward kernel once a
                     step) and the little XLA around them; the masked form
                     with its float32 scores in HBM is a CPU run's. The
                     reader sums by scope and knows neither form.
``dsa.layer_share_pct``  their sum over the time of the capture's programs
                     on ``XLA Modules``.
``dsa.attend_roofline_pct``  the least time the chip could take for the
                     attention the mathematics needs — the larger of
                     ``attend_flops`` over the peak bf16 rate and
                     ``attend_bytes`` over the peak HBM rate
                     (``lib/peaks.json``) — over ``dsa.attend_ms``. The
                     operations are those of the SELECTED (query, key)
                     pairs, forward and backward once: products of
                     unselected keys and recomputation earn nothing, so no
                     implementation reads over 100%.

``dsa.kept_keys_pct`` (program counter) comes from a probe before the
window: the first batch through the run's own weights with the
``"dsa_stats"`` collection mutable, published by ``parallel/
sparse_attention.py::publish_dsa_stats`` (gauge ``bps_dsa_kept_keys_ratio``:
(query, key) pairs attended over the causal ones, all layers; 43.75% at
s 8192, topk 2048 — or the selection is not what it says).

A program without the scopes or the collection reports nothing.
"""

import os

LAYER = "sparse attention"
SCOPES = {"indexer": "bps.dsa.indexer", "select": "bps.dsa.select",
          "attend": "bps.dsa.attend"}
METRICS = {
    "dsa.indexer_ms": {"unit": "ms", "better": "lower",
                       "source": "device_trace", "moves": "step_ms_p50"},
    "dsa.select_ms": {"unit": "ms", "better": "lower",
                      "source": "device_trace", "moves": "step_ms_p50"},
    "dsa.attend_ms": {"unit": "ms", "better": "lower",
                      "source": "device_trace", "moves": "step_ms_p50"},
    "dsa.layer_share_pct": {"unit": "%", "better": "lower",
                            "source": "device_trace",
                            "moves": "step_ms_p50"},
    "dsa.attend_roofline_pct": {"unit": "%", "better": "higher",
                                "source": "device_trace",
                                "moves": "mfu_pct"},
    "dsa.kept_keys_pct": {"unit": "%", "better": "lower",
                          "source": "program_counter",
                          "moves": "tokens_per_s_per_chip"},
}


# --------------------------------------------------------------------------
# What the attention of one step needs, from shapes alone.

def selected_pairs(seq_len: int, topk: int) -> int:
    """(query, key) pairs of one sequence: query t attends min(t + 1,
    topk) keys."""
    if seq_len <= topk:
        return seq_len * (seq_len + 1) // 2
    return topk * (topk + 1) // 2 + (seq_len - topk) * topk


def attend_flops(rows: int, seq_len: int, topk: int, heads: int,
                 head_dim: int, layers: int) -> int:
    """A selected pair costs, per query head, a QK product and a PV product
    of ``head_dim`` multiply-adds, forward, and twice that backward: 12 x
    heads x head_dim operations."""
    return (layers * rows * selected_pairs(seq_len, topk)
            * 12 * heads * head_dim)


def attend_bytes(rows: int, seq_len: int, heads: int, kv_heads: int,
                 head_dim: int, layers: int, operand_bytes: int = 2) -> int:
    """Every operand once in the dtype the matmuls read (bf16): forward
    reads q, k, v and writes the output; backward reads q, k, v and the
    output's gradient and writes the three gradients."""
    q, kv = heads * head_dim, kv_heads * head_dim
    per_token = (q + 2 * kv + q) + (q + 2 * kv + q) + (q + 2 * kv)
    return layers * rows * seq_len * per_token * operand_bytes


def attend_roofline_pct(attend_ms: float, cfg: dict, rows: int,
                        peaks: dict) -> float:
    heads, kv_heads, head_dim, layers = (
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"], cfg["num_hidden_layers"])
    s, topk = cfg["seq_len"], cfg["sa_config"]["topk"]
    least_s = max(
        attend_flops(rows, s, topk, heads, head_dim, layers)
        / peaks["bf16_flops_per_s"],
        attend_bytes(rows, s, heads, kv_heads, head_dim, layers)
        / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (attend_ms * 1e-3)


# --------------------------------------------------------------------------

def split_ms(ops, steps: int) -> dict:
    """Per step, ms under each of the three scopes. Empty where none
    shows."""
    sums = dict.fromkeys(SCOPES, 0)
    for _, tf_op, duration_ps in ops:
        for key, scope in SCOPES.items():
            if scope in tf_op:
                sums[key] += duration_ps
                break
    if not steps or not any(sums.values()):
        return {}
    return {k: v * 1e-9 / steps for k, v in sums.items()}


def setup(run):
    """The probe: how many keys the first batch's queries attend, with the
    run's own weights."""
    stats_of = getattr(run.config, "layer_stats", None)
    if stats_of is None:
        return
    from byteps_tpu.parallel.sparse_attention import publish_dsa_stats

    stats = stats_of(run.cfg, run.rows // run.chips)   # one chip's batch
    if "dsa_stats" in stats:
        run.probes.update(publish_dsa_stats(stats["dsa_stats"]))


def read(run):
    ratio = run.probes.get("bps_dsa_kept_keys_ratio")
    out = {"dsa.kept_keys_pct": None if ratio is None else 100.0 * ratio}
    if run.trace is None:
        return out
    from benchmark.layers import moe
    from benchmark.lib import device, trace_reduce

    xplane = trace_reduce.find_xplane(os.path.join(run.out_dir, "trace"))
    steps = run.trace["steps"]
    ms = split_ms(moe.scoped_ops(xplane, run.layout), steps)
    programs_ms = sum(d for _, _, d in moe.scoped_ops(
        xplane, run.layout, run.layout.module_line)) * 1e-9 / steps
    if not ms or not programs_ms:
        return out
    out.update({f"dsa.{k}_ms": v for k, v in ms.items()})
    out["dsa.layer_share_pct"] = 100.0 * sum(ms.values()) / programs_ms
    if ms["attend"]:
        import jax

        out["dsa.attend_roofline_pct"] = attend_roofline_pct(
            ms["attend"], run.cfg, run.rows // run.chips,
            device.peaks(jax.devices()[0].device_kind))
    return out


if __name__ == "__main__":
    # python3 benchmark/layers/dsa.py <trace_dir> <steps> [out.json.gz]
    # prints the three sums for a capture a traced run left behind; with a
    # third argument it also writes the capture's scoped ops for
    # tests/benchmark/data: [name cut to 48, tf_op, summed ps, events] per
    # distinct (name, tf_op), and the programs' summed ps.
    import gzip
    import json
    import sys

    sys.path.insert(0, __file__.rsplit("/benchmark/", 1)[0])
    from benchmark.layers import moe
    from benchmark.lib import trace_reduce

    xplane = trace_reduce.find_xplane(sys.argv[1])
    ops = moe.scoped_ops(xplane, trace_reduce.TPU)
    print(json.dumps(split_ms(ops, int(sys.argv[2]))))
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

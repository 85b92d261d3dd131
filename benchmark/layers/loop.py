"""Layer: looped stack (``byteps_tpu/models/ouro.py``: one stack of blocks
run ``total_ut_steps`` times over shared weights, an exit after every pass).

From the device trace, first device, line ``XLA Ops``, per traced step, by
the program's ``jax.named_scope``s in the ``tf_op`` stat of an event's
metadata (``layers/moe.py::scoped_ops`` reads it; nothing of that file is
copied):

``loop.stack_ms``    ``bps.loop.stack``: the blocks and the final norm of
                     every pass — forward, the forward recomputed in the
                     backward pass, and backward.
``loop.exit_ms``     ``bps.loop.exit``: gates, heads, per-pass
                     cross-entropies and the objective, the same three ways.
``loop.layer_share_pct``  their sum over the time of the capture's programs
                     on ``XLA Modules``.
``loop.stack_roofline_pct``  the least time the chip could take for the
                     passes the mathematics needs — the larger of
                     ``stack_flops`` over the peak bf16 rate and
                     ``stack_bytes`` over the peak HBM rate
                     (``lib/peaks.json``) — over ``loop.stack_ms``. Forward
                     and backward ONCE: the recomputed forward earns
                     nothing, so no implementation reads over 100%.

The loop itself is a ``%while`` on that line, an event as long as all the
ops of its body, which are events of their own: it carries no scope (the
scopes are opened inside the body) and a container is skipped by name as
well, so nothing is counted twice.

``loop.mean_exit_pass`` (program counter) comes from a probe before the
window: the first batch through the run's own weights with the
``"loop_stats"`` collection mutable, published by ``models/ouro.py::
publish_loop_stats`` (gauge ``bps_loop_mean_exit_pass``: sum_r r x the mean
over positions of p_t(r); 1.875 at the zero gate of the initialisation — or
the exit distribution is not what it says). ``probes.loop_block_applications``
on the diagnostics line is R x L.

A program without the scopes or the collection reports nothing.
"""

import os

LAYER = "looped stack"
SCOPES = {"stack": "bps.loop.stack", "exit": "bps.loop.exit"}
CONTAINERS = ("%while", "%conditional", "%call")
METRICS = {
    "loop.stack_ms": {"unit": "ms", "better": "lower",
                      "source": "device_trace", "moves": "step_ms_p50"},
    "loop.exit_ms": {"unit": "ms", "better": "lower",
                     "source": "device_trace", "moves": "step_ms_p50"},
    "loop.layer_share_pct": {"unit": "%", "better": "lower",
                             "source": "device_trace",
                             "moves": "step_ms_p50"},
    "loop.stack_roofline_pct": {"unit": "%", "better": "higher",
                                "source": "device_trace",
                                "moves": "mfu_pct"},
    "loop.mean_exit_pass": {"unit": "passes", "better": "lower",
                            "source": "program_counter",
                            "moves": "tokens_per_s_per_chip"},
}


# --------------------------------------------------------------------------
# What the passes of one step need, from shapes alone.

def block_matmul_params(d: int, m: int) -> int:
    """Q, K, V, O and gate, up, down; the norms' scales are not matmuls."""
    return 4 * d * d + 3 * d * m


def stack_flops(tokens: int, seq_len: int, passes: int, layers: int, d: int,
                m: int) -> int:
    """A block application costs a token 6 operations a matmul parameter
    (forward, input gradient, weight gradient) and 12 s d of attention,
    halved for the causal triangle; ``passes x layers`` applications."""
    per_token = 6 * block_matmul_params(d, m) + 12 * seq_len * d // 2
    return passes * layers * per_token * tokens


def stack_bytes(tokens: int, passes: int, layers: int, d: int, m: int,
                operand_bytes: int = 2) -> int:
    """A block application reads its weights once forward and once backward
    in the dtype the matmuls read (bf16), and moves q, k, v and the
    attention's output once: forward reads three and writes one, backward
    reads those three and the output's gradient and writes three
    gradients — 11 tensors of width d."""
    per_application = (2 * block_matmul_params(d, m)
                       + tokens * 11 * d) * operand_bytes
    return passes * layers * per_application


def stack_roofline_pct(stack_ms: float, cfg: dict, rows: int,
                       peaks: dict) -> float:
    d, m, s = cfg["hidden_size"], cfg["intermediate_size"], cfg["seq_len"]
    passes, layers = cfg["total_ut_steps"], cfg["num_hidden_layers"]
    least_s = max(
        stack_flops(rows * s, s, passes, layers, d, m)
        / peaks["bf16_flops_per_s"],
        stack_bytes(rows * s, passes, layers, d, m)
        / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (stack_ms * 1e-3)


# --------------------------------------------------------------------------

def split_ms(ops, steps: int) -> dict:
    """Per step, ms under each of the two scopes. Empty where neither
    shows."""
    sums = dict.fromkeys(SCOPES, 0)
    for name, tf_op, duration_ps in ops:
        if name.startswith(CONTAINERS):
            continue
        for key, scope in SCOPES.items():
            if scope in tf_op:
                sums[key] += duration_ps
                break
    if not steps or not any(sums.values()):
        return {}
    return {k: v * 1e-9 / steps for k, v in sums.items()}


def setup(run):
    """The probe: where the first batch's positions leave the loop, with
    the run's own weights."""
    stats_of = getattr(run.config, "loop_stats", None)
    if stats_of is None or not getattr(run.config, "FIRST", None):
        return
    try:
        from byteps_tpu.models.ouro import publish_loop_stats
    except ImportError:              # a program without the looped model
        return
    published = publish_loop_stats(
        stats_of(run.cfg, run.rows // run.chips))      # one chip's batch
    run.probes.update(published)
    if published:
        run.probes["loop_block_applications"] = int(
            published["bps_loop_block_applications_total"])


def read(run):
    out = {"loop.mean_exit_pass": run.probes.get("bps_loop_mean_exit_pass")}
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
    out.update({f"loop.{k}_ms": v for k, v in ms.items()})
    out["loop.layer_share_pct"] = 100.0 * sum(ms.values()) / programs_ms
    if ms["stack"]:
        import jax

        out["loop.stack_roofline_pct"] = stack_roofline_pct(
            ms["stack"], run.cfg, run.rows // run.chips,
            device.peaks(jax.devices()[0].device_kind))
    return out


if __name__ == "__main__":
    # python3 benchmark/layers/loop.py <trace_dir> <steps> [out.json.gz]
    # prints the two sums for a capture a traced run left behind; with a
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

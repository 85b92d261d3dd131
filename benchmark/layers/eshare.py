"""Layer: expert share (``byteps_tpu/parallel/moe.py::dropless_moe_ffn``
told which experts it holds, in whichever model stacks it).

The expert layer's reader (``layers/moe.py``) for a chip that holds a share
of the experts: the one reader of every gated share. From the device trace,
first device, line ``XLA Ops``, per traced step (``layers/kda.py::
capture_ms`` reads the capture once a run for all the readers of a cell),
every op goes to the first of these parts that takes it, so no op counts
twice (``split_ms``):

``gmm``            the grouped matmuls: the ``%ragged-dot`` kernels BY NAME,
                   as ``moe.gmm_ms`` (``layers/moe.py::GMM_KERNEL``). On the
                   chip the events of a share's pass carry no scope (their
                   ``tf_op`` ends ``.../moe/cond/branch_1_fun/
                   jit(_held_pass)/ragged-dot``), so a sum by scope alone
                   leaves them out.
``router``         what runs under ``bps.moe.router``, where a program
                   writes that scope: a router that is a network of its own
                   (down-projection, depth averaging, MLP), forward,
                   recomputed and backward.
``route``          what runs under ``bps.moe.route`` — the gate, top-k,
                   sort, the gather of all T k rows, un-permute and
                   combine, and their gradients — and the
                   ``ragged-dot-metadata`` helpers. ``bps.moe.router``
                   begins with ``bps.moe.route``: the longer name is asked
                   first, so the router's time is not in here.
``experts_other``  the rest of ``bps.moe.experts``: the casts of the float32
                   expert weights to bf16, the activation, gradient sums.
``shared``         ``bps.moe.shared``, where a program writes it: the shared
                   expert every token passes.

``eshare.gmm_ms``, ``eshare.router_ms``, ``eshare.route_ms``: those parts,
recomputation included; ``router_ms`` only where the scope shows.
``eshare.layer_share_pct``: the five parts together over the time of the
capture's programs on ``XLA Modules``: the expert layers' share of the step.
``eshare.gmm_roofline_pct``: the least time the chip could take for the
grouped matmuls the mathematics needs — gate, up and down, each forward,
dgrad and wgrad once, over the rows that reached the held experts (the
probe's count for the first batch, every expert layer), each operand once in
bf16 with the held experts' weights only — over ``eshare.gmm_ms``. Rows
beyond the held groups and recomputed calls earn nothing. The expert layers
are counted by the probe (the leaves of ``moe_stats``, one [E] count a
layer), not read from ``num_hidden_layers``: a stack with a leading dense
layer has fewer, and one with an expert layer outside the stack more.
``eshare.held_load`` (program counter): the assignments that reached the
held experts over their even part T k H / E, from the same probe before the
window — the first batch through the run's own weights with the
``"moe_stats"`` collection mutable (``publish_moe_stats(..., held=...)``,
gauge ``bps_moe_held_load``; 1 at even routing). A reading of the
initialisation: ``layers/share.py`` reads the same probe at the window's
end.

What differs between programs is what they wrote — a scope is there or not,
the kernels are there or not — and nothing here asks which program it is.

By hand at even routing, 8,192 tokens a step choosing 8 of 256 experts, for
three stacks whose depth of 5 is not their count of expert layers
(``gmm_flops`` 9 x 2 x rows x d x m over 197 TFLOP/s, ``gmm_bytes`` 9 x 2 x
(rows x (d + m) + layers x held x d x m) over 819 GB/s):
- 8 held, d 2304, m 1024, layer 0 dense, 4 expert layers: 4 x 8,192 x 8 x
  8 / 256 = 8,192 rows; 347.9 GFLOP = 1.766 ms; 1.850 GB = 2.258 ms, bound
  by bandwidth. Five layers would read 2.823 ms, a quarter too much.
- 8 held, d 2048, m 768, layer 0 dense, 4 expert layers in the stack and one
  more in the multi-token module behind it, 5 in all: 10,240 rows; 289.9
  GFLOP = 1.472 ms; 1.652 GB = 2.016 ms, bound by bandwidth. The stack's
  depth is 5 too, by chance and not by rule.
- 16 held, d 2048, m 512, layer 0 dense, 4 expert layers: 16,384 rows; 309.2
  GFLOP = 1.570 ms; 1.963 GB = 2.397 ms, bound by bandwidth. Five layers
  would read 2.996 ms.

The diagnostics line (``probes``) gets ``eshare_held_rows`` and
``eshare_expert_layers`` from the probe, ``eshare_experts_other_ms`` and
``eshare_shared_ms`` from the capture.

A program without the kernels, the scopes or the collection reports nothing.
"""

LAYER = "expert share"
# asked in this order: ``bps.moe.router`` holds ``bps.moe.route``
SCOPES = {"router": "bps.moe.router", "route": "bps.moe.route",
          "experts_other": "bps.moe.experts", "shared": "bps.moe.shared"}
METRICS = {
    "eshare.gmm_ms": {"unit": "ms", "better": "lower",
                      "source": "device_trace", "moves": "step_ms_p50"},
    "eshare.router_ms": {"unit": "ms", "better": "lower",
                         "source": "device_trace", "moves": "step_ms_p50"},
    "eshare.route_ms": {"unit": "ms", "better": "lower",
                        "source": "device_trace", "moves": "step_ms_p50"},
    "eshare.layer_share_pct": {"unit": "%", "better": "lower",
                               "source": "device_trace",
                               "moves": "step_ms_p50"},
    "eshare.gmm_roofline_pct": {"unit": "%", "better": "higher",
                                "source": "device_trace",
                                "moves": "mfu_pct"},
    "eshare.held_load": {"unit": "ratio", "better": "lower",
                         "source": "program_counter",
                         "moves": "tokens_per_s_per_chip"},
}
CALLS = 9     # gate, up and down, each forward, dgrad and wgrad


def gmm_flops(held_rows: int, d: int, m: int) -> int:
    """``held_rows``: the assignments to held experts, all layers. Every
    call multiplies each through one d x m matrix."""
    return CALLS * 2 * held_rows * d * m


def gmm_bytes(held_rows: int, held: int, d: int, m: int, layers: int,
              operand_bytes: int = 2) -> int:
    """Every call reads two and writes one of: the rows at width d, the rows
    at width m, the held experts' d x m weights (or their gradient), in each
    of the ``layers`` expert layers."""
    return CALLS * operand_bytes * (held_rows * (d + m)
                                    + layers * held * d * m)


def gmm_roofline_pct(gmm_ms: float, cfg: dict, held_rows: int, peaks: dict,
                     expert_layers: int) -> float:
    d, m = cfg["hidden_size"], cfg["moe_intermediate_size"]
    least_s = max(
        gmm_flops(held_rows, d, m) / peaks["bf16_flops_per_s"],
        gmm_bytes(held_rows, cfg["num_local_experts"], d, m, expert_layers)
        / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (gmm_ms * 1e-3)


def split_ms(ops, steps: int) -> dict:
    """Per step, ms: the five parts of the docstring, each op in one of them
    at most, containers skipped. Empty where none of them shows."""
    from benchmark.layers import kda, moe

    sums = dict.fromkeys(("gmm", *SCOPES), 0)
    for name, tf_op, duration_ps in ops:
        if name.startswith(kda.CONTAINERS):
            continue
        if moe.GMM_KERNEL.match(name):
            sums["gmm"] += duration_ps
        elif name.startswith("%ragged-dot-metadata"):
            sums["route"] += duration_ps
        else:
            for key, scope in SCOPES.items():
                if scope in tf_op:
                    sums[key] += duration_ps
                    break
    if not steps or not any(sums.values()):
        return {}
    return {k: v * 1e-9 / steps for k, v in sums.items()}


def setup(run):
    """The probe: which experts the first batch's tokens reach, with the
    run's own weights, how many of the assignments are held here, and how
    many expert layers counted them."""
    stats_of = getattr(run.config, "layer_stats", None)
    if stats_of is None:
        return
    import jax

    from byteps_tpu.parallel.moe import publish_moe_stats

    stats = stats_of(run.cfg, run.rows // run.chips)   # one chip's batch
    if "moe_stats" not in stats:
        return
    first, held = run.config.FIRST_EXPERT, run.cfg["num_local_experts"]
    run.probes.update(publish_moe_stats(stats["moe_stats"],
                                        held=(first, held)))
    counts = jax.tree_util.tree_leaves(stats["moe_stats"])
    run.probes["eshare_expert_layers"] = len(counts)
    run.probes["eshare_held_rows"] = int(sum(
        c[first:first + held].sum() for c in counts))


def read(run):
    out = {"eshare.held_load": run.probes.get("bps_moe_held_load")}
    if run.trace is None:
        return out
    from benchmark.layers import kda

    ops, programs_ms, steps = kda.capture_ms(run)
    ms = split_ms(ops, steps)
    if not ms or not programs_ms:
        return out
    run.probes["eshare_experts_other_ms"] = ms["experts_other"]
    run.probes["eshare_shared_ms"] = ms["shared"]
    out["eshare.route_ms"] = ms["route"]
    out["eshare.layer_share_pct"] = 100.0 * sum(ms.values()) / programs_ms
    if ms["router"]:
        out["eshare.router_ms"] = ms["router"]
    if ms["gmm"]:
        out["eshare.gmm_ms"] = ms["gmm"]
        held_rows = run.probes.get("eshare_held_rows")
        layers = run.probes.get("eshare_expert_layers")
        if held_rows and layers:
            import jax

            from benchmark.lib import device

            out["eshare.gmm_roofline_pct"] = gmm_roofline_pct(
                ms["gmm"], run.cfg, held_rows,
                device.peaks(jax.devices()[0].device_kind), layers)
    return out

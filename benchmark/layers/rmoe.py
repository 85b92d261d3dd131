"""Layer: expert share, ungated experts (``byteps_tpu/parallel/moe.py::
dropless_moe_ffn`` with ``w_gate`` None: a sigmoid gate over 128 experts,
top-6 renormalised and scaled by 2.5, told which 8 it holds, every expert
``down(relu(up(x))^2)`` — two grouped matmuls, not three — plus an ungated
shared expert twice as wide, inside ``models/kimi_linear.py::KimiSparseMoe``
as ``models/nemotron_h.py`` stacks it: four expert layers, each a layer of
its own, 98,304 assignments each sorted, 8 grouped matmuls of about 768 rows
x 2688 x 1856).

``layers/eshare.py``'s reader under this layer's names, which a test outside
the benchmark holds (its ``setup`` and ``read`` are called, nothing of it is
copied): ``rmoe.gmm_ms``, ``rmoe.route_ms``, ``rmoe.layer_share_pct`` and
``rmoe.held_load`` are ``eshare.*`` of those names, word for word — the
``%ragged-dot`` kernels by name, the route scope, the share of the capture's
program time that the kernels, the route scope, the rest of
``bps.moe.experts`` and the shared expert under ``bps.moe.shared`` take
together, the probe's count of the rows at the held experts. One figure is
this reader's own:

``rmoe.gmm_roofline_pct``  ``eshare.gmm_roofline_pct`` with the calls this
                  body has: **six** grouped matmuls a layer the mathematics
                  needs — up and down, each forward, dgrad and wgrad once —
                  where SwiGLU has nine, over the rows that reached the held
                  experts (the probe's count for the first batch, every
                  expert layer), each operand once in bf16 with the held
                  experts' weights only, and the EXPERT layers of the
                  pattern (4 of the 9 layers), over ``rmoe.gmm_ms``.

By hand at even routing: 4 layers x 16,384 x 6 x 8 / 128 = 24,576 held
rows; 6 x 2 x 24,576 x 2688 x 1856 = 1.471 TFLOP, 7.47 ms at the peak;
bytes 6 x 2 x (24,576 x (2688 + 1856) + 4 x 8 x 2688 x 1856) = 3.26 GB,
3.98 ms: bound by arithmetic on this count.

A program without the kernels, the scopes or the collection reports nothing.
"""

LAYER = "expert share, ungated experts"
METRICS = {
    "rmoe.gmm_ms": {"unit": "ms", "better": "lower",
                    "source": "device_trace", "moves": "step_ms_p50"},
    "rmoe.route_ms": {"unit": "ms", "better": "lower",
                      "source": "device_trace", "moves": "step_ms_p50"},
    "rmoe.layer_share_pct": {"unit": "%", "better": "lower",
                             "source": "device_trace",
                             "moves": "step_ms_p50"},
    "rmoe.gmm_roofline_pct": {"unit": "%", "better": "higher",
                              "source": "device_trace", "moves": "mfu_pct"},
    "rmoe.held_load": {"unit": "ratio", "better": "lower",
                       "source": "program_counter",
                       "moves": "tokens_per_s_per_chip"},
}
FROM_ESHARE = ("route_ms", "gmm_ms", "layer_share_pct", "held_load")
CALLS = 6     # up and down, each forward, dgrad and wgrad


def gmm_flops(held_rows: int, d: int, m: int) -> int:
    """``layers/eshare.py``'s count at this body's calls: ``held_rows`` the
    assignments to held experts, all layers; every call multiplies each
    through one d x m matrix."""
    from benchmark.layers import eshare

    return eshare.gmm_flops(held_rows, d, m) * CALLS // eshare.CALLS


def gmm_bytes(held_rows: int, held: int, d: int, m: int, layers: int) -> int:
    """Likewise: every call reads two and writes one of the rows at width
    d, the rows at width m and the held experts' d x m weights (or their
    gradient), in bf16."""
    from benchmark.layers import eshare

    return (eshare.gmm_bytes(held_rows, held, d, m, layers) * CALLS
            // eshare.CALLS)


def gmm_roofline_pct(gmm_ms: float, cfg: dict, held_rows: int,
                     peaks: dict) -> float:
    d, m = cfg["hidden_size"], cfg["moe_intermediate_size"]
    least_s = max(
        gmm_flops(held_rows, d, m) / peaks["bf16_flops_per_s"],
        gmm_bytes(held_rows, cfg["num_local_experts"], d, m,
                  cfg["hybrid_override_pattern"].count("E"))
        / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (gmm_ms * 1e-3)


def setup(run):
    from benchmark.layers import eshare

    eshare.setup(run)


def read(run):
    from benchmark.layers import eshare

    out = {"rmoe." + name.partition(".")[2]: value
           for name, value in eshare.read(run).items()
           if name.partition(".")[2] in FROM_ESHARE}
    held_rows = run.probes.get("eshare_held_rows")
    if out.get("rmoe.gmm_ms") and held_rows:
        import jax

        from benchmark.lib import device

        out["rmoe.gmm_roofline_pct"] = gmm_roofline_pct(
            out["rmoe.gmm_ms"], run.cfg, held_rows,
            device.peaks(jax.devices()[0].device_kind))
    return out

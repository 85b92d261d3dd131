"""Layer: gated memory unit (``models/phi4_flash.py::GatedMemoryUnit``:
``W_2 (m . SiLU(LN(h) W_1))`` with ``m`` [s, 5120] the memory the last
Mamba-1 layer of the self-decoder hands to every such layer: two products
2560 <-> 5120 and an elementwise gate, in place of a token mixer).

From the device trace, first device, line ``XLA Ops``, per traced step
(``layers/kda.py::capture_ms`` reads the capture once for the cell's
readers), over every such layer:

``gmu.unit_ms``  what runs under ``bps.gmu``: both products and the gate —
                 forward, the forward recomputed in the backward pass, and
                 backward, the memory's cotangent among it.

By hand a layer's products are 6 x 16,384 x 26,214,400 = 2.577 TFLOP a step,
13.08 ms at the peak bf16 rate.

A program without the scope reports nothing.
"""

LAYER = "gated memory unit"
SCOPES = {"unit": "bps.gmu"}
METRICS = {
    "gmu.unit_ms": {"unit": "ms", "better": "lower",
                    "source": "device_trace", "moves": "step_ms_p50"},
}


def read(run):
    if run.trace is None:
        return {}
    from benchmark.layers import kda

    ops, programs_ms, steps = kda.capture_ms(run)
    ms = kda.scoped_ms(ops, SCOPES, steps)
    if not ms or not programs_ms:
        return {}
    return {"gmu.unit_ms": ms["unit"]}

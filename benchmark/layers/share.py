"""Layer: the share's window (what a cell that holds one chip's share of a
sparse model measures while it measures: ``lib/loop.py``'s window over
``byteps_tpu/parallel/moe.py::dropless_moe_ffn`` told which experts it
holds, with no exchange to answer for the experts held elsewhere).

A share's step depends on its routing — the held experts' rows, and past
``held_row_bound`` a second pass over them — and the routing is free to
move inside a window: the router learns that only the held experts answer.
The cell's other readers read the window's first traced steps and a probe
before the first step; this one reads the window as a whole and its end.

``share.step_drift_pct`` (host clock): the mean of the window's last two log
                   intervals over the mean of the first two after the
                   traced steps, less one, in percent. A traced run goes on
                   to the window's end after its traced steps
                   (``lib/cell.py::_window``) and ``run.window.step_s``
                   holds the whole series, one entry a log interval, so
                   there is no second capture. A window of fewer than six
                   log intervals, or of fewer than four after the traced
                   ones, reports nothing: never 0.
``share.held_load_end`` (program counter): the cell's own ``*.held_load``
                   probe once more — the run's first batch through the
                   model with the ``"moe_stats"`` collection mutable, the
                   assignments that reached the held experts over their
                   even part T k H / E, all layers together — on the
                   parameters the window ENDS with. ``lib/cell.py`` calls
                   ``finish(run, state)`` after the peak of memory is read
                   and before the state is dropped, so the probe is in no
                   timed window and in no ``peak_hbm_gb``. Every run takes
                   it, traced or not: ``probes.share_held_load_end`` and
                   ``probes.share_held_load_end_by_layer`` stand on the
                   diagnostics line of each.

Read beside the cell's ``*.held_load`` (the same probe at the first step):
the two within a tenth of each other and a drift inside +-3% say that the
window's ``step_ms_p50`` is of steps that ran the routing the traced steps
ran. A configuration without the collection reports no ``held_load_end``.
"""

import math

LAYER = "share window"
METRICS = {
    "share.step_drift_pct": {"unit": "%", "better": "lower",
                             "source": "host_clock", "moves": "step_ms_p50"},
    "share.held_load_end": {"unit": "ratio", "better": "lower",
                            "source": "program_counter",
                            "moves": "step_ms_p50"},
}
ENDS = 2              # log intervals averaged at either end
LEAST_INTERVALS = 6   # of the whole window, the traced ones among them


def step_drift_pct(step_s, traced_intervals: int):
    """``step_s``: seconds a step, one entry a log interval, the traced
    intervals first. None where the window is too short to have two ends."""
    rest = step_s[traced_intervals:]
    if len(step_s) < LEAST_INTERVALS or len(rest) < 2 * ENDS:
        return None
    first, last = sum(rest[:ENDS]), sum(rest[-ENDS:])
    return 100.0 * (last / first - 1.0)


def held_load(counts, first_expert: int, held: int) -> float:
    """``counts``: one [E] array of assignments a layer. The assignments
    that reached the held experts over their even part, all layers together
    (``publish_moe_stats``'s ``bps_moe_held_load``, worked out here)."""
    return float(
        sum(c[first_expert:first_expert + held].sum() for c in counts)
        / sum(c.sum() * held / c.size for c in counts))


def finish(run, state):
    """The probe on the parameters the window ended with."""
    model_of = getattr(run.config, "_model", None)
    first = getattr(run.config, "FIRST", None)
    if model_of is None or not first:
        return
    import jax
    import numpy as np

    model = model_of(run.cfg)

    @jax.jit
    def stats(params, tokens):
        return model.apply(params, tokens, mutable=["moe_stats"])[1]

    counted = stats(state[0], first["tokens"][:run.rows // run.chips])
    counts = [np.asarray(c) for c in jax.tree_util.tree_leaves(
        dict(counted).get("moe_stats", {}))]
    if not counts:
        return
    where = run.config.FIRST_EXPERT, run.cfg["num_local_experts"]
    run.probes["share_held_load_end"] = held_load(counts, *where)
    run.probes["share_held_load_end_by_layer"] = [
        held_load([c], *where) for c in counts]


def read(run):
    out = {"share.held_load_end": run.probes.get("share_held_load_end")}
    if run.window is not None:
        out["share.step_drift_pct"] = step_drift_pct(
            run.window.step_s, math.ceil(
                run.traffic["trace_steps"] / run.traffic["log_every"]))
    return out

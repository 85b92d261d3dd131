"""Layer: step builders on the PS leg (``byteps_tpu/jax/training.py``'s serial
step, ``jax/bucketed.py``, ``jax/overlap.py``): how much of the leg a step
design hides under the device's programs, from the program's own spans.

All three designs write one vocabulary (``byteps_tpu.jax.ps.ALL_SPANS``,
``docs/timeline.md``) onto ``/host:CPU`` of the capture, on the clock of the
device planes. One traced step is ``[start of bps.step.grad, end of the next
bps.step.apply]`` on the caller's thread; its leg runs from the first byte
enqueued into the C core — the start of the step's earliest ``bps.ps.stage``
(the binding: the serial and the bucketed step) or ``bps.tap.push`` (the taps,
on the runtime's threads) — to the end of its ``bps.ps.wait``, the last handle
settled. Per traced step, then the median over the steps:

``psleg.first_push_ms``  start of the step's first program on the first device
                         (``XLA Modules``) to that first enqueue: how long the
                         device computes before a byte is on its way.
``psleg.leg_ms``         first enqueue to last settle.
``psleg.hidden_ms``      the part of the leg during which a program ran on
                         the first device (the leg's interval cut with the
                         union of ``XLA Modules`` intervals). The serial step
                         reads 0 by construction.
``psleg.exposed_ms``     ``leg_ms`` − ``hidden_ms``, of the medians reported:
                         what the step pays for the leg.
``psleg.round_ms``       the C core's round as elapsed time:
                         ``layers/round.py``'s own ``read`` called and its
                         ``round.elapsed_ms`` handed on under this name
                         (that entry's ``workloads`` list is not a later PR's
                         to append to). ``leg_ms`` − ``round_ms`` is what
                         feeding and settling add around the round.

``SPANS`` goes to the reduction (``lib/cell.py``), so ``breakdown.idle_gaps``
names the device's idle stretches by the program's spans. A capture without
the spans — a program from before they existed — reports nothing.

    python3 benchmark/layers/psleg.py <trace_dir> [--rounds <saved round summary>]

prints each step's split for a capture a traced run left behind
(``.benchmark_out/<cell>/trace``), how long each traced step took by the
benchmark's own step span, and, with the rows of a saved
``ffi.round_summary()`` (a traced run of a cell with this reader leaves them
in ``.benchmark_out/<cell>/round_summary.json``), each traced step's round
mapped onto the capture's clock through the ``mono_ns`` stat of the step's
``bps.step.ps``, with how far inside the leg its two ends lie.
"""

import os
import re
import statistics
import sys

LAYER = "step builders, PS overlap"
STEP_GRAD, STEP_PS, STEP_APPLY = "bps.step.grad", "bps.step.ps", "bps.step.apply"
STAGE, WAIT, H2D, TAP_PUSH = ("bps.ps.stage", "bps.ps.wait", "bps.ps.h2d",
                              "bps.tap.push")
SPANS = (STEP_GRAD, STEP_PS, STEP_APPLY, STAGE, WAIT, H2D, TAP_PUSH)
PARTS = ("first_push", "leg", "hidden", "exposed")
ROUNDS_FILE = "round_summary.json"     # a traced run's, in the cell's out_dir
METRICS = {
    "psleg.first_push_ms": {"unit": "ms", "better": "lower",
                            "source": "program_span", "moves": "step_ms_p50"},
    "psleg.leg_ms": {"unit": "ms", "better": "lower",
                     "source": "program_span", "moves": "step_ms_p50"},
    "psleg.hidden_ms": {"unit": "ms", "better": "higher",
                        "source": "program_span", "moves": "step_ms_p50"},
    "psleg.exposed_ms": {"unit": "ms", "better": "lower",
                         "source": "program_span", "moves": "step_ms_p50"},
    "psleg.round_ms": {"unit": "ms", "better": "lower",
                       "source": "program_counter", "moves": "step_ms_p50"},
}


def split_steps(events, layout) -> list:
    """One dict per traced step, in time order. In nanoseconds on the
    capture's clock: ``step`` (start of ``bps.step.grad``, end of the next
    ``bps.step.apply``), ``leg_ns`` (first enqueue, last settle), ``step_ps``
    (the span) and ``first_program`` (its start); in milliseconds the four
    parts (``PARTS``), and ``enqueues``, the number of enqueue spans. A step
    with no enqueue span or no ``bps.ps.wait`` inside it is left out, a part
    that cannot be told (no program on the device's line) out of its dict."""
    from benchmark.lib.trace_reduce import clip, length, union

    host_re, device_re = re.compile(layout.host_plane), re.compile(
        layout.device_plane)
    spans = {name: [] for name in SPANS}
    programs = {}                      # device plane -> [(start, end)]
    for plane, line, name, start, dur in events:
        if name in spans:
            if host_re.match(plane):
                spans[name].append((start, start + dur))
        elif line == layout.module_line and device_re.match(plane):
            programs.setdefault(plane, []).append((start, start + dur))
    progs = sorted(programs[min(programs)]) if programs else []
    busy = union(progs)
    applies, legs = sorted(spans[STEP_APPLY]), sorted(spans[STEP_PS])
    enqueues = sorted(spans[STAGE] + spans[TAP_PUSH])

    steps, last_apply = [], -1
    for lo, _ in sorted(spans[STEP_GRAD]):
        apply_lo, hi = next((a for a in applies if a[0] >= lo), (None, None))
        if hi is None:
            continue
        pushed = [s for s, _ in enqueues if lo <= s <= hi]
        settled = [e for s, e in spans[WAIT] if lo <= s <= hi]
        if not pushed or not settled:
            continue
        first, end = pushed[0], max(settled)
        hidden_ns = length(clip(busy, first, end))
        step = {"step": (lo, hi), "leg_ns": (first, end),
                "step_ps": next((p for p in legs if lo <= p[0] <= hi), None),
                "enqueues": len(pushed), "leg": (end - first) / 1e6,
                "hidden": hidden_ns / 1e6,
                "exposed": (end - first - hidden_ns) / 1e6}
        # The step's first program: dispatched inside bps.step.grad, so it
        # starts no earlier — and it is not the apply program of the step
        # before, which the device may only reach (its uploads landed) once
        # this step's bps.step.grad has begun.
        begin = next((s for s, _ in progs if s >= lo and s > last_apply),
                     None)
        if begin is not None:
            step["first_program"] = begin
            step["first_push"] = (first - begin) / 1e6
        # this step's apply program: the first to start after its dispatch
        last_apply = next((s for s, _ in progs if s >= apply_lo), last_apply)
        steps.append(step)
    return steps


def reduce_spans(events, layout) -> dict:
    """{metric: median over the capture's steps}; empty without the spans.
    ``psleg.exposed_ms`` is the difference of the two medians it is defined
    by, so the three add up on the line as they do in every step."""
    steps = split_steps(events, layout)
    out = {}
    for part in PARTS[:3]:
        values = [s[part] for s in steps if part in s]
        if values:
            out[f"psleg.{part}_ms"] = statistics.median(values)
    if steps:
        out["psleg.exposed_ms"] = (out["psleg.leg_ms"]
                                   - out["psleg.hidden_ms"])
    return out


def read(run):
    import json

    from benchmark.layers import round as round_reader

    out = {}
    elapsed = round_reader.read(run).get("round.elapsed_ms")
    if elapsed is not None:
        out["psleg.round_ms"] = elapsed
    if run.trace is not None:
        out.update(reduce_spans(run.events, run.layout))
        # beside the capture, for this file's command: the rounds' rows
        if "round_summary_after" in run.counters:
            with open(os.path.join(run.out_dir, ROUNDS_FILE), "w") as f:
                json.dump(run.counters["round_summary_after"], f)
    return out


def align(rounds, steps, anchors) -> list:
    """One entry per traced step whose round is among ``rounds``: the round
    whose first enqueue, put on the capture's clock, falls inside the step.
    ``anchors`` are ``(start_ns, mono_ns)`` of the capture's ``bps.step.ps``
    spans: ``mono_ns`` is ``CLOCK_MONOTONIC`` — the core's ``NowUs()`` — read
    at the span's start, so ``start_ns - mono_ns`` is the one shift of the
    whole capture (the median over the spans is taken). Margins in ms: the
    round's start after the step's first enqueue began, its end before the
    step's ``bps.ps.wait`` ended; and against ``bps.step.ps`` itself, inside
    which the taps' rounds do not begin. Negative means outside."""
    if not anchors:
        return []
    shift = int(statistics.median(start - mono for start, mono in anchors))
    out = []
    for step in steps:
        lo, hi = step["step"]
        inside = [r for r in rounds
                  if lo <= r["start_us"] * 1000 + shift <= hi]
        if not inside:
            continue
        r = inside[0]
        begin = r["start_us"] * 1000 + shift
        end = begin + r["elapsed_us"] * 1000
        first, settled = step["leg_ns"]
        row = {"round": r["round"], "round_start_ns": begin,
               "round_end_ns": end,
               "start_margin_ms": (begin - first) / 1e6,
               "end_margin_ms": (settled - end) / 1e6}
        if step["step_ps"]:
            row["step_ps_start_margin_ms"] = (begin - step["step_ps"][0]) / 1e6
            row["step_ps_end_margin_ms"] = (step["step_ps"][1] - end) / 1e6
        out.append(row)
    return out


def step_ps_anchors(xplane_path: str) -> list:
    """``(start_ns, mono_ns)`` of every ``bps.step.ps`` of a capture that
    carries the stat, in time order."""
    import jax

    data = jax.profiler.ProfileData.from_file(xplane_path)
    found = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == STEP_PS:
                    stats = {k: v for k, v in ev.stats}
                    if "mono_ns" in stats:
                        found.append((int(ev.start_ns),
                                      int(stats["mono_ns"])))
    return sorted(found)


def main(argv) -> int:
    import argparse
    import json

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from benchmark.lib import loop, trace_reduce

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--rounds")
    args = ap.parse_args(argv)
    xplane = trace_reduce.find_xplane(args.trace_dir)
    events = trace_reduce.read_events(xplane)
    layout = trace_reduce.TPU
    steps = split_steps(events, layout)
    reduced = trace_reduce.reduce_events(
        events, steps=max(1, len(steps)), spans=loop.SPANS + SPANS,
        step_span=loop.STEP_SPAN, layout=layout, top=20)
    out = {"metrics": reduce_spans(events, layout),
           "steps": [{k: v for k, v in s.items()
                      if k in PARTS + ("enqueues",)} for s in steps],
           # the benchmark's own span of a whole step, loss fetch included:
           # what a traced step took, to hold against an untraced run's
           "traced_step_ms": [dur / 1e6 for _, _, name, _, dur in events
                              if name == loop.STEP_SPAN],
           "idle_gaps": reduced and reduced["idle_gaps"]}
    if args.rounds:
        with open(args.rounds) as f:
            out["alignment"] = align(json.load(f)["rounds"], steps,
                                     step_ps_anchors(xplane))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

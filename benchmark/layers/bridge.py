"""Layer: host boundary (``byteps_tpu/jax/ps.py``), from the program's own spans.

The PS leg of a step, split where it happens. ``jax/ps.py`` and
``jax/training.py`` write eight ``TraceAnnotation`` spans (``SPANS``, a mirror
of ``byteps_tpu.jax.ps.SPANS``) onto ``/host:CPU`` of the capture, on the
clock of the device planes, so a host span can be cut at a device event.
Per traced step, then the median over the steps:

``bridge.push_pull_ms``  ``bps.ps.push_pull``, whole (bridge thread).
``bridge.d2h_ms``        the part of ``bps.ps.d2h`` after the end of the
                         gradient program on the first device: ``device_get``
                         first waits for that program, and the wait is
                         compute, not boundary.
``bridge.stage_ms``      ``bps.ps.stage``: staging buffers, enqueue into the
                         C core.
``bridge.wait_ms``       ``bps.ps.wait``: until every handle has settled.
``bridge.h2d_ms``        from the start of ``bps.ps.h2d`` to the start of the
                         next program on the first device: ``device_put``
                         returns before the bytes have landed, the apply
                         program starts when they have.

``SPANS`` also goes to the reduction (``lib/cell.py``), which splits the
device's idle gaps over these spans and the benchmark's own:
``breakdown.idle_gaps`` then names ``bps.ps.stage``, ``bps.ps.wait`` and the
rest where it read ``bench.step`` for the whole PS leg. A capture without
the spans — a program from before they existed — reports nothing.

    python3 benchmark/layers/bridge.py <trace_dir>

prints the same reduction for a capture a traced run left behind
(``.benchmark_out/<cell>/trace``), with each step's parts, the bridge
hand-off, how much of ``bps.ps.push_pull`` its children cover, and the
device's idle time split over the spans (the reduction's ``idle_gaps``).
"""

import os
import re
import statistics
import sys

LAYER = "host boundary"
SPANS = ("bps.step.grad", "bps.step.ps", "bps.step.apply",
         "bps.ps.push_pull", "bps.ps.d2h", "bps.ps.stage", "bps.ps.wait",
         "bps.ps.h2d")
STEP_PS, PUSH_PULL, D2H, STAGE, WAIT, H2D = SPANS[1], *SPANS[3:]
PARTS = ("push_pull", "d2h", "stage", "wait", "h2d")
METRICS = {
    f"bridge.{part}_ms": {"unit": "ms", "better": "lower",
                          "source": "program_span", "moves": "step_ms_p50"}
    for part in PARTS
}


def split_steps(events, layout) -> list:
    """One dict per ``bps.ps.push_pull`` span of the capture, in time order:
    the five parts and the hand-off in milliseconds, ``children_cover`` as a
    share. A part that cannot be told (no ``bps.ps.h2d`` inside, no program
    after it) is left out of that step's dict."""
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

    steps = []
    for lo, hi in sorted(spans[PUSH_PULL]):
        inside = {name: next(((s, e) for s, e in spans[name]
                              if lo <= s and e <= hi), None)
                  for name in (D2H, STAGE, WAIT, H2D)}
        ms = {"push_pull": (hi - lo) / 1e6}
        for part, name in (("stage", STAGE), ("wait", WAIT)):
            if inside[name]:
                ms[part] = (inside[name][1] - inside[name][0]) / 1e6
        if inside[D2H]:
            s, e = inside[D2H]
            # the gradient program: the last one that began before the
            # fetch returned
            ends = [pe for ps, pe in progs if ps < e]
            cut = max(s, ends[-1]) if ends else s
            ms["d2h"] = max(0, e - cut) / 1e6
        if inside[H2D]:
            s = inside[H2D][0]
            following = [ps for ps, _ in progs if ps >= s]
            if following:
                ms["h2d"] = (following[0] - s) / 1e6
        if all(inside.values()):
            ms["children_cover"] = sum(
                e - s for s, e in inside.values()) / (hi - lo)
        outer = next(((s, e) for s, e in spans[STEP_PS]
                      if s <= lo and hi <= e), None)
        if outer:
            ms["handoff"] = (outer[1] - outer[0] - (hi - lo)) / 1e6
        steps.append(ms)
    return steps


def reduce_spans(events, layout) -> dict:
    """{metric: median over the capture's steps}; empty without the spans."""
    steps = split_steps(events, layout)
    out = {}
    for part in PARTS:
        values = [s[part] for s in steps if part in s]
        if values:
            out[f"bridge.{part}_ms"] = statistics.median(values)
    return out


def read(run):
    if run.trace is None:
        return {}
    return reduce_spans(run.events, run.layout)


def main(argv) -> int:
    import argparse
    import json

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from benchmark.lib import loop, trace_reduce

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    args = ap.parse_args(argv)
    events = trace_reduce.read_events(trace_reduce.find_xplane(args.trace_dir))
    layout = trace_reduce.TPU
    steps = split_steps(events, layout)
    reduced = trace_reduce.reduce_events(
        events, steps=len(steps), spans=loop.SPANS + SPANS,
        step_span=loop.STEP_SPAN, layout=layout, top=20)
    print(json.dumps({
        "metrics": reduce_spans(events, layout), "steps": steps,
        "idle_gaps": reduced and reduced["idle_gaps"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

"""Cut a recorded event list out of a capture, for ``tests/benchmark/data``.

    JAX_PLATFORMS=cpu python benchmark/dump_events.py <trace_dir | list.json.gz> <out.json.gz> [--steps N] [--planes N]

Keeps the events of the first ``--planes`` device planes, the benchmark's
host spans and those the readers name (``SPANS`` of every file in
``benchmark/layers/``), from the start of the first step span to the end of the
``--steps``-th step's last program on the first device (all traced steps and
all planes by default), as ``[plane, line, name, start_ns, duration_ns]``
with names cut to 160 characters and times counted from the first step
span. Prints the capture's planes and lines first: look before you reduce.
"""

import argparse
import glob
import gzip
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.lib import cell, loop, trace_reduce  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("source")
    ap.add_argument("out")
    ap.add_argument("--steps", type=int)
    ap.add_argument("--planes", type=int)
    args = ap.parse_args()
    if os.path.isdir(args.source):
        events = trace_reduce.read_events(
            trace_reduce.find_xplane(args.source))
    else:
        with gzip.open(args.source, "rt") as f:
            events = [tuple(e) for e in json.load(f)["events"]]
    lines = {}
    for p, l, n, s, d in events:
        row = lines.setdefault((p, l), {"events": 0, "first_ns": s,
                                        "names": []})
        row["events"] += 1
        if len(row["names"]) < 3:
            row["names"].append(n[:120])
    for (p, l), row in sorted(lines.items()):
        print(json.dumps({"plane": p, "line": l, **row}))

    layout = trace_reduce.TPU
    readers = [cell.load_module(path, "benchmark_layer")
               for path in sorted(glob.glob(os.path.join(
                   REPO, "benchmark", "layers", "*.py")))]
    spans = {loop.STEP_SPAN, *loop.SPANS, *cell.reader_spans(readers)}
    host = sorted((e for e in events if e[2] in spans
                   and re.match(layout.host_plane, e[0])),
                  key=lambda e: e[3])
    all_steps = [e for e in host if e[2] == loop.STEP_SPAN]
    steps = all_steps[:args.steps]
    planes = sorted({e[0] for e in events
                     if re.match(layout.device_plane, e[0])})[:args.planes]
    programs = sorted((e for e in events if e[0] == planes[0]
                       and e[1] == layout.module_line), key=lambda e: e[3])
    per_step = len(programs) // len(all_steps)
    lo = steps[0][3]
    last = programs[per_step * len(steps) - 1]
    hi = max(last[3] + last[4], steps[-1][3] + steps[-1][4])
    kept_steps = {(e[3], e[4]) for e in steps}

    def keep(e):
        if not (lo <= e[3] and e[3] + e[4] <= hi):
            return False
        if e[2] == loop.STEP_SPAN:
            return (e[3], e[4]) in kept_steps
        if e[2] in spans:   # inside a kept step span
            return any(s <= e[3] and e[3] + e[4] <= s + d
                       for s, d in kept_steps)
        return e[0] in planes

    out = sorted(([p, l, n[:160], s - lo, d] for p, l, n, s, d in events
                  if keep((p, l, n, s, d))), key=lambda e: (e[0], e[1], e[3]))
    with gzip.open(args.out, "wt") as f:
        json.dump({"steps": len(steps), "events": out}, f)
    print(json.dumps({"out": args.out, "steps": len(steps),
                      "events": len(out), "bytes": os.path.getsize(args.out),
                      "planes": sorted({e[0] for e in out})}))


if __name__ == "__main__":
    main()

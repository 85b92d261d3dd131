"""From a ``jax.profiler`` capture to numbers, in two parts.

``read_events`` is the thin reader: one ``.xplane.pb`` → a list of events
``(plane, line, name, start_ns, duration_ns)``. ``reduce_events`` is the
reduction over that list and touches no file, so it is checked against
small recorded lists with hand-worked answers (``tests/benchmark/data``)
and every later PR computes the same numbers the same way.

What a TPU capture looks like (jax 0.9.0 / libtpu 0.0.34; PR 21 finding 8
and this PR's one- and four-chip captures, looked at by hand): one plane per
chip, ``/device:TPU:<n>``. Its line ``XLA Modules`` holds one event per
executed program, ``XLA Ops`` the operations inside them back to back,
``Async XLA Ops`` what overlaps them (prefetch copies and slices; only chip
0's plane has this line), ``Steps`` the profiler's own grouping (one per
program, not per training step). Today's step has its collectives on
``XLA Ops``: synchronous ``all-reduce``, nothing beside them. Event names
are raw HLO text (``%fusion.12 = f32[8,1024]{1,0} fusion(...)``): the
program has no named scopes yet. Host spans written with ``TraceAnnotation``
land on the ``/host:CPU`` plane, line ``python3``, on the same clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict

# Shorter gaps between back-to-back operations are the device's own
# scheduling, not the host holding it back; they stay in the idle share.
MIN_GAP_NS = 1000
COLLECTIVE = re.compile(
    r"^%?(all-reduce|reduce-scatter|all-gather|all-to-all|collective-permute)")


@dataclasses.dataclass(frozen=True)
class Layout:
    """Where a capture keeps what. ``TPU`` is the only layout the command
    uses; the CPU rehearsal steers another from outside."""

    device_plane: str                  # regex over plane names
    op_lines: tuple                    # lines whose events are device work
    sync_line: str                     # the one whose ops run back to back
    module_line: str                   # line with one event per program
    host_plane: str = r"^/host:CPU$"


TPU = Layout(device_plane=r"^/device:TPU:\d+$",
             op_lines=("XLA Ops", "Async XLA Ops"), sync_line="XLA Ops",
             module_line="XLA Modules")


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(
            f"expected one .xplane.pb under {trace_dir}: {paths}")
    return paths[0]


def read_events(xplane_path: str) -> list:
    """Every event of the capture as (plane, line, name, start_ns,
    duration_ns), times as integers."""
    import jax

    data = jax.profiler.ProfileData.from_file(xplane_path)
    return [(plane.name, line.name, ev.name, int(ev.start_ns),
             int(ev.duration_ns))
            for plane in data.planes for line in plane.lines
            for ev in line.events]


# --------------------------------------------------------------------------
# Interval arithmetic on lists of (start, end).

def union(intervals) -> list:
    """Sorted, disjoint intervals covering the same points."""
    out = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def length(disjoint) -> int:
    return sum(end - start for start, end in disjoint)


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(disjoint, other_disjoint) -> list:
    """The part of ``disjoint`` that ``other_disjoint`` does not cover."""
    out, j = [], 0
    for start, end in disjoint:
        cur = start
        while j < len(other_disjoint) and other_disjoint[j][1] <= cur:
            j += 1
        k = j
        while k < len(other_disjoint) and other_disjoint[k][0] < end:
            o_start, o_end = other_disjoint[k]
            if o_start > cur:
                out.append([cur, o_start])
            cur = max(cur, o_end)
            k += 1
        if cur < end:
            out.append([cur, end])
    return out


# --------------------------------------------------------------------------

def op_label(name: str, limit: int = 100) -> str:
    """``%fusion.12 = f32[8,1024]{1,0} fusion(...)`` → ``fusion.12
    f32[8,1024]``: HLO name and result shape, raw until the program has
    named scopes."""
    lhs, sep, rhs = name.partition(" = ")
    label = lhs.lstrip("%")
    if sep:
        rhs = re.sub(r"\{[^}]*\}", "", rhs)      # layouts
        end = rhs.find(")") + 1 if rhs.startswith("(") else rhs.find(" ")
        label = f"{label} {rhs[:end] if end > 0 else rhs}"
    return label[:limit]


def is_collective(name: str) -> bool:
    return bool(COLLECTIVE.match(name.partition(" = ")[0]))


def reduce_events(events, *, steps: int, spans, step_span: str,
                  layout: Layout = TPU, top: int = 10):
    """Reduce one capture of ``steps`` training steps. Returns None when
    the capture holds no device operation. All times in seconds; per-step
    figures divide by ``steps``. ``spans`` are the host span names to look
    for (the benchmark's own and those the cell's readers name),
    ``step_span`` the name of the benchmark's per-step span."""
    device_re = re.compile(layout.device_plane)
    host_re = re.compile(layout.host_plane)
    span_names = set(spans) | {step_span}
    ops = defaultdict(list)        # plane -> [(start, end, name, sync)]
    programs = defaultdict(list)   # plane -> [(start, end, name)]
    host = []                      # (start, end, name) of benchmark spans
    for plane, line, name, start, dur in events:
        if name in span_names:
            if host_re.match(plane):
                host.append((start, start + dur, name))
            continue
        if not device_re.match(plane):
            continue
        if line == layout.module_line:
            programs[plane].append((start, start + dur, name))
        elif layout.op_lines is None or line in layout.op_lines:
            ops[plane].append((start, start + dur, name,
                               layout.sync_line in (None, line)))
    planes = sorted(p for p, evs in ops.items() if evs)
    if not planes:
        return None

    # The traced window: from the first step span's start to the later of
    # the last benchmark span's end (the closing loss fetch waits for the
    # device) and the last device operation's end (a list cut from a
    # capture may end before that fetch). Without host spans, the extent
    # of the device's own events.
    step_spans = [h for h in host if h[2] == step_span]
    hi = max(e for p in planes for _, e, _, _ in ops[p])
    if step_spans:
        lo = min(s for s, _, _ in step_spans)
        hi = max(hi, max(e for _, e, _ in host))
    else:
        lo = min(s for p in planes for s, _, _, _ in ops[p])
    window = hi - lo
    if window <= 0:
        return None

    busy = {p: union(clip([(s, e) for s, e, _, _ in ops[p]], lo, hi))
            for p in planes}
    busy_ns = sum(length(b) for b in busy.values()) / len(planes)

    first = planes[0]
    in_window = [(max(s, lo), min(e, hi), n, sync)
                 for s, e, n, sync in ops[first] if min(e, hi) > max(s, lo)]
    # A program is the window's if it ENDS after the window's start: the
    # first traced step's may start a hair before the step span that
    # dispatched it (the device's clock against the host's), while the
    # warm-up's last ended before the loss fetch that closed the warm-up.
    # (And if it starts before the window's end: a list cut from a capture
    # ends at its last operation, 1 us before that operation's program.)
    progs = [(s, e, n) for s, e, n in programs[first] if e > lo and s < hi]

    # Collectives on any op line (an asynchronous one spans start to done);
    # exposed is the part during which no other operation of the
    # back-to-back line runs. The longest operations are that line's too:
    # asynchronous spans overlap them and would be counted twice.
    coll = union([(s, e) for s, e, n, _ in in_window if is_collective(n)])
    other = union([(s, e) for s, e, n, sync in in_window
                   if sync and not is_collective(n)])
    exposed = subtract(coll, other)

    totals = defaultdict(int)
    for s, e, n, sync in in_window:
        if sync:
            totals[op_label(n)] += e - s
    device_ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]

    # Idle gaps of the first device by what the host was doing in them. A
    # gap is split at span boundaries and every stretch goes to the
    # SHORTEST span over it, on whichever thread: a PS step idles in one
    # gap, from the gradient program's end to the apply program's start,
    # and the span at its middle would be given all of it. What only the
    # step span covers lies between its calls; the rest, between steps.
    idle = [g for g in subtract([[lo, hi]], busy[first])
            if g[1] - g[0] >= MIN_GAP_NS]
    covers = (sorted((e - s, s, e, n) for s, e, n in host if n != step_span)
              + sorted((e - s, s, e, f"{n} (between calls)")
                       for s, e, n in step_spans)
              + [(hi - lo, lo, hi, "between steps")])
    gaps = defaultdict(int)
    for _, start, end, name in covers:
        claimed = length(clip(idle, start, end))
        if claimed:
            gaps[name] += claimed
            idle = subtract(idle, [[start, end]])
    idle_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]

    ns = 1e-9
    return {
        "devices": len(planes),
        "window_s": window * ns,
        "busy_s": busy_ns * ns,
        "idle_share": 1.0 - busy_ns / window,
        "steps": steps,
        "programs": len(progs),
        "program_s_per_step": sum(e - s for s, e, _ in progs) * ns / steps,
        "programs_per_step": len(progs) / steps,
        "collective_s_per_step": length(coll) * ns / steps,
        "exposed_collective_s_per_step": length(exposed) * ns / steps,
        "device_ops": [[n, t * ns] for n, t in device_ops],
        "idle_gaps": [[n, t * ns] for n, t in idle_gaps],
    }

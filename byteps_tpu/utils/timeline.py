"""Communication trace timeline (Chrome trace-event JSON).

Capability parity: the reference's built-in timeline (SURVEY.md §5
"Tracing / profiling": BYTEPS_TRACE_ON / BYTEPS_TRACE_DIR /
BYTEPS_TRACE_START_STEP / BYTEPS_TRACE_END_STEP; per-partition stage
timestamps dumped as Chrome trace-event JSON per rank).

Two sources feed the timeline:
- the C++ core's per-partition stage spans (compress / push / pull),
  drained via ``bps_dump_trace`` — the DCN leg;
- ``jax.profiler`` for the on-device stages (the ICI leg), started and
  stopped over the same step window so both views line up.

Usage::

    tl = Timeline()            # reads BYTEPS_TRACE_* from the config
    for batch in data:
        step(...)
        tl.step()              # call once per training step
    tl.close()                 # idempotent; also dumps on end-step
"""

from __future__ import annotations

import os
from typing import Optional

from byteps_tpu.config import Config, get_config


class Timeline:
    """Step-windowed trace recorder (reference: BytePSContext timestamps +
    the trace dump on BYTEPS_TRACE_END_STEP)."""

    def __init__(self, config: Optional[Config] = None,
                 *, device_trace: bool = True):
        self._cfg = config or get_config()
        self._enabled = self._cfg.trace_on
        self._device_trace = device_trace
        self._step = 0
        self._profiling = False
        self._dumped = False
        self._device_dir: Optional[str] = None
        self._anchor_us: Optional[int] = None
        if self._enabled:
            os.makedirs(self._cfg.trace_dir, exist_ok=True)

    @property
    def active(self) -> bool:
        """True while the current step is inside the trace window."""
        return (self._enabled and not self._dumped
                and self._step >= self._cfg.trace_start_step)

    def step(self) -> None:
        """Mark the end of one training step."""
        if not self._enabled or self._dumped:
            return
        self._step += 1
        # Report the step to the C core so its ring enforces the
        # BYTEPS_TRACE_START_STEP/END_STEP window too — a core-only
        # long run no longer records outside the window (ISSUE 5).
        self._report_core_step(self._step)
        if (self._step >= self._cfg.trace_start_step
                and not self._profiling and self._device_trace
                and self._step < self._cfg.trace_end_step):
            self._start_device_trace()
        if self._step >= self._cfg.trace_end_step:
            self.close()

    @staticmethod
    def _report_core_step(step: int) -> None:
        try:
            import byteps_tpu.core.ffi as ffi
            if ffi._lib is not None:  # never trigger a core build here
                ffi._lib.bps_trace_step(int(step))
        except Exception:
            pass  # collective-mode runs have no C core; tracing is soft

    def close(self) -> None:
        """Dump both trace sources and the combined timeline (idempotent)."""
        if not self._enabled or self._dumped:
            return
        self._dumped = True
        self._stop_device_trace()
        core_path = self._dump_core_trace()
        # Combined capture (SURVEY.md §5: interop with jax.profiler/XPlane):
        # device + host stages on ONE timeline.
        if core_path and self._device_dir and self._anchor_us is not None:
            try:
                merge_core_device_traces(
                    core_path, self._device_dir,
                    os.path.join(self._cfg.trace_dir,
                                 f"combined_rank{self._rank()}.json"),
                    self._anchor_us)
            except Exception:
                pass  # the per-source dumps above remain usable

    # --- internals ---------------------------------------------------------

    def _rank(self) -> int:
        try:
            import byteps_tpu.jax as bps
            if bps.initialized():
                return bps.rank()
        except Exception:
            pass
        return self._cfg.worker_id

    def _dump_core_trace(self):
        """Drain the C++ worker's per-partition spans into Chrome JSON.
        Returns the path, or None when no PS client is live."""
        try:
            import byteps_tpu.jax as bps
            client = bps._st().ps_client if bps.initialized() else None
        except Exception:
            client = None
        if client is None:
            return None
        path = os.path.join(self._cfg.trace_dir,
                            f"comm_rank{self._rank()}.json")
        client.dump_trace(path)
        return path

    def _start_device_trace(self) -> None:
        try:
            import time

            import jax
            self._device_dir = os.path.join(
                self._cfg.trace_dir, f"device_rank{self._rank()}")
            jax.profiler.start_trace(self._device_dir)
            # Fallback anchor of the two clock domains, for a capture
            # without a bps.step.ps or bps.ps.push_pull span (which carry the
            # measured relation, see merge_core_device_traces): the C core stamps
            # spans with CLOCK_MONOTONIC microseconds ==
            # time.monotonic_ns()//1000 here, sampled once start_trace
            # has returned.
            self._anchor_us = time.monotonic_ns() // 1000
            self._profiling = True
        except Exception:
            self._profiling = False
            self._device_dir = None
            self._anchor_us = None

    def _stop_device_trace(self) -> None:
        if self._profiling:
            import jax
            try:
                jax.profiler.stop_trace()
            finally:
                self._profiling = False


# --- combined device + DCN timeline (SURVEY.md §5 XPlane interop) -----------

_DCN_PID = 900000  # far above real pids; its own process row in the viewer


def find_device_chrome_trace(device_dir: str) -> Optional[str]:
    """Locate the Chrome-trace JSON that ``jax.profiler.stop_trace`` wrote
    under ``device_dir`` (the TensorBoard trace-viewer file:
    ``plugins/profile/<run>/<host>.trace.json.gz``)."""
    import glob
    paths = glob.glob(os.path.join(device_dir, "plugins", "profile", "*",
                                   "*.trace.json.gz"))
    return max(paths, key=os.path.getmtime) if paths else None


def capture_clock_offset_us(device_events) -> Optional[float]:
    """CLOCK_MONOTONIC µs minus the capture's own ``ts``, read off the
    capture: every PS step builder stamps its ``bps.step.ps`` span, and
    ``jax/ps.py`` every ``bps.ps.push_pull``, with ``mono_ns``
    (``time.monotonic_ns()`` at the span's start — the C core's ``NowUs()``
    clock), and the profiler gives the same instant as the event's ``ts``.
    The first of either is taken. None when the capture holds neither
    (collective mode, or a window without a PS step)."""
    from byteps_tpu.jax.ps import SPAN_PUSH_PULL, SPAN_STEP_PS

    for e in device_events:
        if (e.get("name") in (SPAN_STEP_PS, SPAN_PUSH_PULL)
                and "mono_ns" in e.get("args", {})):
            return int(e["args"]["mono_ns"]) / 1e3 - e["ts"]
    return None


def merge_core_device_traces(core_path: str, device_dir: str,
                             out_path: str, anchor_monotonic_us: int) -> int:
    """Merge the C core's DCN spans into the jax.profiler device trace —
    one Chrome JSON with device and host-comm stages on a single timeline.

    The core stamps spans in CLOCK_MONOTONIC µs; the device trace uses its
    own µs timebase starting near ``start_trace``. The relation between
    the two is measured, from a ``bps.step.ps`` or ``bps.ps.push_pull`` span
    of the capture (``capture_clock_offset_us``), so from any of the three
    PS step designs. Only a capture without one falls back
    to the guess ``anchor_monotonic_us`` — the monotonic clock sampled
    after ``start_trace`` returned, which can lie tens of ms after the
    capture's ts 0. Returns the number of merged core events.
    """
    import gzip
    import json

    dev_file = find_device_chrome_trace(device_dir)
    if dev_file is None:
        raise FileNotFoundError(f"no trace.json.gz under {device_dir}")
    with gzip.open(dev_file, "rt") as f:
        dev = json.load(f)
    with open(core_path) as f:
        core = json.load(f)

    events = list(dev.get("traceEvents", []))
    offset_us = capture_clock_offset_us(events)
    if offset_us is None:
        offset_us = anchor_monotonic_us
    events.append({"name": "process_name", "ph": "M", "pid": _DCN_PID,
                   "args": {"name": "byteps DCN (C core)"}})
    n = 0
    for e in core.get("traceEvents", []):
        if "ts" not in e:
            continue
        shifted = dict(e)
        shifted["pid"] = _DCN_PID
        shifted["ts"] = e["ts"] - offset_us
        events.append(shifted)
        n += 1
    dev["traceEvents"] = events
    with open(out_path, "w") as f:
        json.dump(dev, f)
    return n

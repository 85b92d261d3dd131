"""The measured loop, the same in every cell.

Dispatch steps without blocking, fetch the losses every ``log_every`` steps
(as ``example/jax/train_imagenet_resnet50_byteps.py`` and users do) and stamp
the host clock there; stop starting steps when the time is up, finish what
is in flight and count its time. Batches come from a pool made before the
window and are placed inside the loop, because users pay that transfer too.

Every step sits in a ``StepTraceAnnotation`` and its three calls in
``TraceAnnotation`` spans, written from here because the program has none
yet. They cost nothing while no profiler runs, so the traced and the
untraced run execute the same code.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
import time

STEP_SPAN = "bench.train"
SPANS = ("bench.place_batch", "bench.step", "bench.fetch_loss")


@dataclasses.dataclass
class Window:
    """What one call of ``measure`` saw."""

    attempted: int = 0                 # steps started
    completed: int = 0                 # steps whose loss came back
    failed: int = 0                    # raised, or a non-finite loss
    wall_s: float = 0.0                # first dispatch to last loss fetched
    step_s: list = dataclasses.field(default_factory=list)  # per log interval
    losses: list = dataclasses.field(default_factory=list)
    error: str = ""

    def merge(self, other: "Window") -> "Window":
        return Window(self.attempted + other.attempted,
                      self.completed + other.completed,
                      self.failed + other.failed, self.wall_s + other.wall_s,
                      self.step_s + other.step_s, self.losses + other.losses,
                      self.error or other.error)


def measure(step, state, pool, place_batch, *, log_every: int,
            seconds: float = math.inf, max_steps: float = math.inf,
            first_step: int = 0):
    """Run ``step`` from ``state`` = (params, opt_state) over the cycled
    ``pool`` until ``seconds`` have passed or ``max_steps`` were started.
    Returns (state, Window). A step that raises ends the window: its state
    was donated."""
    import jax

    win = Window()
    params, opt_state = state
    pending = []
    t_start = t_mark = time.perf_counter()
    deadline = t_start + seconds

    def fetch():
        nonlocal t_mark
        with jax.profiler.TraceAnnotation(SPANS[2]):
            values = [float(x) for x in jax.device_get(pending)]
        now = time.perf_counter()
        win.step_s.append((now - t_mark) / len(pending))
        t_mark = now
        win.completed += len(values)
        win.failed += sum(not math.isfinite(v) for v in values)
        win.losses.extend(values)
        pending.clear()

    try:
        while win.attempted < max_steps and time.perf_counter() < deadline:
            n = first_step + win.attempted
            with jax.profiler.StepTraceAnnotation(STEP_SPAN, step_num=n):
                with jax.profiler.TraceAnnotation(SPANS[0]):
                    batch = place_batch(pool[n % len(pool)])
                win.attempted += 1
                with jax.profiler.TraceAnnotation(SPANS[1]):
                    params, opt_state, loss = step(params, opt_state, batch)
                pending.append(loss)
                if len(pending) == log_every:
                    fetch()
        if pending:
            fetch()
    except Exception as e:  # the run reports it: failed, correct false
        win.failed += win.attempted - win.completed
        win.error = f"{type(e).__name__}: {e}"
    win.wall_s = time.perf_counter() - t_start
    return (params, opt_state), win


def step_ms_p50(win: Window) -> float:
    return 1e3 * statistics.median(win.step_s)

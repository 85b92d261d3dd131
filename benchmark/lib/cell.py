"""One run of one cell: set-up, window, checks, the result line's content.

Driven by data: the cell's entry in ``BENCHMARK.json`` names a configuration
and a traffic mix; ``benchmark/configs/<config>.json|.py``,
``benchmark/traffic/<traffic>.json`` and ``benchmark/layers/<reader>.py`` are
found by those names. Nothing here knows a cell, a model or a metric of a
layer by name.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import importlib.util
import json
import math
import os
import re
import shutil
import statistics
import sys
import time
import types

from benchmark.lib import device as device_lib
from benchmark.lib import loop, reference, trace_reduce

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass(frozen=True)
class Steer:
    """What the command fixes and only a test steers from outside
    (``tests/benchmark``): the command has no option for any of it."""

    sizing: dict = dataclasses.field(default_factory=dict)  # over the config
    platform: str = "tpu"
    layout: trace_reduce.Layout = trace_reduce.TPU
    peaks: dict = None                 # instead of peaks.json's row
    compile_cache: bool = True


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def resolve(ref: str):
    """``module:attr.attr`` → the object."""
    module, _, attrs = ref.partition(":")
    obj = importlib.import_module(module)
    for attr in attrs.split("."):
        obj = getattr(obj, attr)
    return obj


def resolve_kwargs(kwargs: dict) -> dict:
    """Values spelled ``@module:attr`` name objects (a Compression, say)."""
    return {k: resolve(v[1:]) if isinstance(v, str) and v.startswith("@")
            else v for k, v in kwargs.items()}


def find_cell(manifest: dict, name: str) -> dict:
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    known = [c["name"] for c in manifest["workloads"]]
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json (known: {known})")


def find_config(manifest: dict, cell: dict) -> dict:
    """The ``configs`` entry of the configuration ``cell`` names."""
    return next(c for c in manifest["configs"]
                if c["name"] == cell["config"])


def metrics_for(manifest: dict, kind: str, cell_name: str) -> dict:
    """The manifest's metrics of ``kind`` that this cell reports."""
    return {m["name"]: m for m in manifest[kind]
            if cell_name in m.get("workloads", [cell_name])}


class CompileCounter:
    """Counts XLA compilations (and loads from the persistent cache: a
    program the process had not seen yet) while it is open."""

    def __init__(self):
        self.count = 0
        self.open = False

    def __call__(self, event, duration, **kwargs):
        if self.open and event == COMPILE_EVENT:
            self.count += 1


def _check_placement(params, batch, chips: int, rows: int) -> list:
    """Every parameter leaf on every chip, the batch split ``chips`` ways:
    code that never saw more than one chip may put everything on the first."""
    import jax

    problems = []
    for leaf in jax.tree_util.tree_leaves(params):
        n = len({s.device for s in leaf.addressable_shards})
        if n != chips:
            problems.append(f"a parameter leaf lives on {n} device(s), "
                            f"not {chips}")
            break
    for leaf in jax.tree_util.tree_leaves(batch):
        per_dev = {s.device: s.data.shape[0] for s in leaf.addressable_shards}
        if len(per_dev) != chips or set(per_dev.values()) != {rows // chips}:
            problems.append(f"batch rows per device: {sorted(per_dev.values())}")
            break
    return problems


def count_all_reduce(hlo_text: str) -> int:
    return len(re.findall(r"= [^=\n]*\ball-reduce(?:-start)?\(", hlo_text))


def load_cell(repo: str, manifest: dict, cell_name: str, steer: Steer):
    """The cell's files, found by the names in its manifest entry."""
    cell = find_cell(manifest, cell_name)
    entry = find_config(manifest, cell)
    bench = os.path.join(repo, "benchmark")
    traffic = load_json(os.path.join(bench, "traffic",
                                     cell["traffic"] + ".json"))
    if traffic["chips"] != cell["chips"]:
        raise SystemExit(f"{cell_name}: BENCHMARK.json says {cell['chips']} "
                         f"chip(s), its traffic file {traffic['chips']}")
    if traffic["mode"] != "ps" and (traffic["env"] or traffic["fleet"]):
        raise SystemExit(f"{cell['traffic']}: a fleet or environment needs "
                         "mode 'ps'")
    out_dir = os.path.join(repo, ".benchmark_out", cell_name)
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    if traffic.get("malloc"):
        from benchmark.lib import fleet

        # before the first large allocation, and before the children start
        fleet.steady_malloc(traffic["malloc"], os.environ)
    cfg = {**load_json(os.path.join(repo, entry["file"])), **steer.sizing}
    return types.SimpleNamespace(
        repo=repo, cell=cell_name, traffic=traffic, chips=traffic["chips"],
        mode=traffic["mode"], out_dir=out_dir, cfg=cfg,
        rows=cfg["batch_per_chip"] * traffic["chips"],   # the global batch
        config=load_module(
            os.path.join(repo, entry["file"][:-len(".json")] + ".py"),
            "benchmark_config"),
        readers=[load_module(os.path.join(bench, "layers", r + ".py"),
                             f"benchmark_layer_{r}")
                 for r in traffic["readers"]],
        # what the readers read
        timings={}, counters={}, probes={}, trace=None, events=None,
        layout=steer.layout, window=None, n_params=0, param_shapes=[])


def _start_job(run, stack: contextlib.ExitStack) -> None:
    """Collective mode: ``bps.init()``. PS mode: build the C core if its
    stamp is stale, start the fleet, then ``bps.init()`` as worker 0. On
    the way out of ``stack``: ``bps.shutdown()``, then every child must have
    exited 0."""
    if run.mode == "ps":
        from benchmark.lib import fleet
        from byteps_tpu.core.build import build

        t = time.perf_counter()
        build(verbose=False)
        run.timings["ccore_build_s"] = time.perf_counter() - t
        t = time.perf_counter()
        stack.enter_context(fleet.ps_fleet(
            run.repo, os.path.join(run.out_dir, "fleet"),
            run.traffic["fleet"], run.traffic["env"]))

    import byteps_tpu.jax as bps

    bps.init()
    stack.callback(bps.shutdown)
    if run.mode == "ps":
        run.timings["fleet_start_s"] = time.perf_counter() - t


def _ps_counters(run, when: str) -> None:
    if run.mode == "ps":
        from benchmark.lib import fleet

        run.counters[f"push_bytes_{when}"] = fleet.pushed_bytes()
        run.counters[f"round_summary_{when}"] = fleet.round_summary()


def _round_stats(run, stat):
    """PS mode, for the log: the window's rounds in the C core's own
    counters (sums over the round's partitions, and its elapsed-time
    stamps), each reduced by ``stat`` (the median; the maximum, which says
    whether a stalled step stalled inside the round)."""
    if run.mode != "ps":
        return None
    after = run.counters["round_summary_after"]
    n = (after["completed_total"]
         - run.counters["round_summary_before"]["completed_total"])
    rounds = after["rounds"][-n:] if 0 < n <= len(after["rounds"]) else []
    return {k: stat(r[k] for r in rounds)
            for k in (rounds[0] if rounds else {}) if k != "round"}


def _window(run, step, state, pool, place, *, seconds, trace, first_step):
    """The measured window. Traced: the first ``trace_steps`` steps under
    the profiler (Python tracer off), then on to ``seconds`` without it.
    Returns (state, Window, traced steps)."""
    import jax

    every = run.traffic["log_every"]
    win, traced = loop.Window(), 0
    if trace:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.raise_error_on_start_failure = True
        jax.profiler.start_trace(os.path.join(run.out_dir, "trace"),
                                 profiler_options=options)
        try:
            state, win = loop.measure(
                step, state, pool, place, log_every=every,
                max_steps=run.traffic["trace_steps"], first_step=first_step)
        finally:
            jax.profiler.stop_trace()
        traced = win.completed
    if not win.error:
        state, rest = loop.measure(
            step, state, pool, place, log_every=every,
            seconds=seconds - win.wall_s,
            first_step=first_step + win.attempted)
        win = win.merge(rest)
    return state, win, traced


def _reference_agreement(run, init_jit, key, tx, pool, losses) -> dict:
    """The plain reference on the same weights and first batches, after the
    window so that its memory is not in the cell's peak."""
    import jax

    step = reference.make_reference_step(
        run.config.reference_loss(run.cfg), tx, micro_batches=max(
            1, run.rows // run.cfg["reference_micro_batch_rows"]))
    batches = [{**b, "weight": run.config.reference_weights(run.cfg, b,
                                                            run.chips)}
               for b in pool[:reference.COMPARED_STEPS]]
    params = init_jit(key)
    ref = reference.reference_losses(step, params, jax.jit(tx.init)(params),
                                     batches)
    return reference.compare_losses(losses, ref)


def _pushed_problem(run, steps: int) -> list:
    """PS mode: one gradient tree crossed the wire per step — not zeros, not
    one tree per chip, not another precision than the traffic file states."""
    if run.mode != "ps":
        return []
    pushed = (run.counters["push_bytes_after"]
              - run.counters["push_bytes_before"])
    want = run.traffic["wire_bytes_per_param"] * run.n_params * steps
    if steps and abs(pushed / want - 1.0) <= 1e-3:
        return []
    return [f"pushed {pushed} B over {steps} steps, expected {want} B "
            "within 0.1%"]


def reader_spans(readers) -> tuple:
    """The host spans the readers name (``SPANS``: what the program writes
    and they read), in the readers' order."""
    return tuple(s for r in readers for s in getattr(r, "SPANS", ()))


def _per_layer(run, manifest, traced: int) -> dict:
    """Reduce the capture, hand it to the cell's readers. Returns the
    traced line's ``metrics``, ``breakdown`` and ``device`` additions."""
    xplane = trace_reduce.find_xplane(os.path.join(run.out_dir, "trace"))
    run.events = trace_reduce.read_events(xplane)
    run.trace = trace_reduce.reduce_events(
        run.events, steps=traced,
        spans=loop.SPANS + reader_spans(run.readers),
        step_span=loop.STEP_SPAN, layout=run.layout)
    if run.trace is None:
        raise RuntimeError(f"no device operation in the capture {xplane}")
    wanted = metrics_for(manifest, "per_layer", run.cell)
    values = {}
    for reader in run.readers:
        for name, value in reader.read(run).items():
            if name in wanted and value is not None:
                values[name] = {"value": value, "unit": wanted[name]["unit"]}
    return {"metrics": values,
            "breakdown": {"device_ops": run.trace["device_ops"],
                          "idle_gaps": run.trace["idle_gaps"]},
            "device": {"busy_s": run.trace["busy_s"],
                       "window_s": run.trace["window_s"]}}


def run_cell(repo: str, manifest: dict, cell_name: str, *, seed: int,
             seconds: float, trace: bool, t0: float,
             steer: Steer = Steer()) -> dict:
    """Run the cell once. Returns the result line's object. ``t0`` is the
    process's start on ``time.perf_counter()``'s clock."""
    run = load_cell(repo, manifest, cell_name, steer)
    cfg, config, traffic, chips = run.cfg, run.config, run.traffic, run.chips

    import jax
    import numpy as np
    import optax

    marks = {"imports_s": time.perf_counter() - t0}

    def mark(name):
        """Set-up, split: seconds since the previous mark."""
        marks[name] = time.perf_counter() - t0 - sum(marks.values())

    if steer.compile_cache:
        from byteps_tpu.utils.compile_cache import enable_compile_cache

        enable_compile_cache()
        # every program of a cell is cached, however quick its compile
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    found = device_lib.require(steer.platform, chips)
    peak_flops = (steer.peaks or device_lib.peaks(found["kind"]))[
        "bf16_flops_per_s"]
    mark("devices_s")

    init, loss_fn = config.build(cfg)
    tx = getattr(optax, cfg["optimizer"]["name"])(**cfg["optimizer"]["kwargs"])
    rows = run.rows
    rng = np.random.default_rng(seed)
    pool = [config.make_batch(cfg, rng, rows)
            for _ in range(traffic["batch_pool"])]
    init_jit = jax.jit(init)
    key = jax.random.PRNGKey(seed)
    mark("batch_pool_s")

    compiles = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    with contextlib.ExitStack() as job:
        _start_job(run, job)
        mark("build_fleet_init_s")
        from byteps_tpu.jax.training import replicate, shard_batch

        step = resolve(traffic["step_builder"])(
            loss_fn, tx, **resolve_kwargs(traffic["builder_kwargs"]))
        params0 = init_jit(key)
        run.param_shapes = [(l.shape, l.dtype)
                            for l in jax.tree_util.tree_leaves(params0)]
        run.n_params = sum(math.prod(s) for s, _ in run.param_shapes)
        opt0 = jax.jit(tx.init)(params0)
        # placed through the library's own helper, as a user does: the step
        # then sees on its first call the shardings it hands back
        state = (replicate(params0), replicate(opt0))
        del params0, opt0
        problems = _check_placement(state[0], shard_batch(pool[0]), chips,
                                    rows)
        mark("weights_s")
        for reader in run.readers:
            if hasattr(reader, "setup"):
                reader.setup(run)
        mark("probes_s")

        # Warm-up: the cell's own programs on the first batches. Their
        # losses are the ones compared with the reference.
        t = time.perf_counter()
        state, warm = loop.measure(step, state, pool, shard_batch,
                                   log_every=1, max_steps=1)
        run.timings["compile_s"] = time.perf_counter() - t
        state, more = loop.measure(
            step, state, pool, shard_batch, log_every=1,
            max_steps=traffic["warmup_steps"] - 1, first_step=1)
        warm = warm.merge(more)
        mark("warmup_s")
        if warm.error:
            raise RuntimeError(f"warm-up failed: {warm.error}")
        _ps_counters(run, "before")

        compiles.open = True
        setup_s = time.perf_counter() - t0
        state, win, traced = _window(
            run, step, state, pool, shard_batch, seconds=seconds,
            trace=trace, first_step=warm.attempted)
        compiles.open = False
        run.window = win
        memory_peak = device_lib.memory_peak_bytes()
        _ps_counters(run, "after")
        # after the window and the peak's reading: a reader's last look at
        # the state the window ends with, in no timed window and no peak
        if not win.error:
            for reader in run.readers:
                if hasattr(reader, "finish"):
                    reader.finish(run, state)
        if chips > 1 and run.mode == "collective" and not win.error:
            hlo = step.lower(*state, shard_batch(pool[0])).compile().as_text()
            if count_all_reduce(hlo) < 1:
                problems.append("the compiled step holds no all-reduce")
            del hlo
        del state
    # bps.shutdown() ran and, in PS mode, every child exited 0.
    jax.monitoring.unregister_event_duration_listener(compiles)
    if not win.completed:
        raise RuntimeError(f"no step completed in the window: {win.error}")

    agreement = _reference_agreement(run, init_jit, key, tx, pool,
                                     warm.losses)
    problems += _pushed_problem(run, win.completed)
    if compiles.count:
        problems.append(f"{compiles.count} compilation(s) inside the window")
    if win.error:
        problems.append(win.error)
    if not agreement["ok"]:
        problems.append("the losses differ from the plain reference")
    failed = warm.failed + win.failed
    result = {"correct": not problems and failed == 0,
              "attempted": win.attempted, "failed": failed}
    device = {**found, "memory_peak_bytes": memory_peak}
    if trace:
        layers = _per_layer(run, manifest, traced)
        result.update(metrics=layers["metrics"],
                      breakdown=layers["breakdown"])
        device.update(layers["device"])
    else:
        rate = win.completed * rows * cfg["seq_len"] / win.wall_s / chips
        values = {
            "tokens_per_s_per_chip": rate,
            "step_ms_p50": loop.step_ms_p50(win),
            "mfu_pct": 100.0 * rate * config.flops_per_token(cfg) / peak_flops,
            "peak_hbm_gb": memory_peak / 1e9,
            "setup_s": setup_s,
        }
        result["metrics"] = {
            name: {"value": values[name], "unit": m["unit"]} for name, m in
            metrics_for(manifest, "end_to_end", cell_name).items()}
    result["device"] = device
    # What the driver ignores and a reader of the log wants.
    print(json.dumps({
        "cell": cell_name, "seed": seed, "seconds": seconds, "trace": trace,
        "steps": win.completed, "step_samples": len(win.step_s),
        "window_s": win.wall_s, "traced_steps": traced,
        "pool_cycles": (warm.attempted + win.attempted) / len(pool),
        "n_params": run.n_params, "problems": problems, "setup_s": setup_s,
        "setup_marks": marks, "timings": run.timings, "agreement": agreement,
        "probes": run.probes, "step_ms_quartiles": [
            1e3 * q for q in (min(win.step_s), *statistics.quantiles(
                win.step_s, n=4, method="inclusive"), max(win.step_s))]
        if len(win.step_s) > 1 else None,
        "step_ms_series": [1e3 * t for t in win.step_s],
        "round_medians_us": _round_stats(run, statistics.median),
        "round_max_us": _round_stats(run, max),
        "rounds": ((run.counters["round_summary_after"]["completed_total"]
                    - run.counters["round_summary_before"]["completed_total"])
                   if run.mode == "ps" else None),
        "last_loss": win.losses[-1]}), file=sys.stderr, flush=True)
    return result

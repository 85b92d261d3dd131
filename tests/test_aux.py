"""Aux subsystems: checkpoint/resume, timeline, callbacks,
broadcast_optimizer_state."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import byteps_tpu.jax as bps
from byteps_tpu.callbacks import (BroadcastGlobalVariablesCallback,
                                  CallbackList, LearningRateWarmupCallback,
                                  MetricAverageCallback, warmup_schedule)
from byteps_tpu.config import Config
from byteps_tpu.utils import (Timeline, latest_step, restore_checkpoint,
                              save_checkpoint)


def _state(rng):
    return {
        "params": {"w": jnp.asarray(rng.standard_normal((4, 3)), jnp.float32),
                   "b": jnp.zeros((3,), jnp.float32)},
        "step": jnp.asarray(7, jnp.int32),
    }


def test_checkpoint_roundtrip(tmp_path, rng):
    base = str(tmp_path / "ckpt")
    state = _state(rng)
    save_checkpoint(base, state, step=10)
    save_checkpoint(base, jax.tree_util.tree_map(lambda x: x + 1, state),
                    step=20)
    assert latest_step(base) == 20

    target = jax.tree_util.tree_map(jnp.zeros_like, state)
    restored, step = restore_checkpoint(base, target, broadcast=False)
    assert step == 20
    np.testing.assert_allclose(np.asarray(restored["params"]["w"]),
                               np.asarray(state["params"]["w"]) + 1)
    # explicit older step
    restored10, step10 = restore_checkpoint(base, target, step=10,
                                            broadcast=False)
    assert step10 == 10
    np.testing.assert_allclose(np.asarray(restored10["params"]["w"]),
                               np.asarray(state["params"]["w"]))


def test_checkpoint_prune(tmp_path, rng):
    base = str(tmp_path / "ckpt")
    state = _state(rng)
    for s in (1, 2, 3, 4, 5):
        save_checkpoint(base, state, step=s, keep=2)
    kept = sorted(os.listdir(base))
    assert kept == ["step_4", "step_5"]


def test_checkpoint_namedtuple_field_order(tmp_path):
    """Regression: NamedTuple fields whose alphabetical order differs from
    declaration order must restore into the RIGHT fields (restore matches
    by tree path, not flatten order)."""
    from typing import NamedTuple

    class TS(NamedTuple):
        step: jnp.ndarray   # 's' sorts after 'b'
        bias: jnp.ndarray

    state = TS(step=jnp.asarray(1.0), bias=jnp.asarray(7.0))
    base = str(tmp_path / "ckpt")
    save_checkpoint(base, state, step=1)
    target = TS(step=jnp.asarray(0.0), bias=jnp.asarray(0.0))
    restored, _ = restore_checkpoint(base, target, broadcast=False)
    assert float(restored.step) == 1.0
    assert float(restored.bias) == 7.0


def test_checkpoint_missing_returns_target(tmp_path, rng):
    target = _state(rng)
    out, step = restore_checkpoint(str(tmp_path / "none"), target)
    assert step is None and out is target


def test_checkpoint_restore_with_broadcast(tmp_path, rng):
    bps.init()
    base = str(tmp_path / "ckpt")
    state = _state(rng)
    save_checkpoint(base, state, step=1)
    restored, step = restore_checkpoint(base, state, broadcast=True)
    assert step == 1
    np.testing.assert_allclose(np.asarray(restored["params"]["w"]),
                               np.asarray(state["params"]["w"]))


def test_broadcast_optimizer_state(rng):
    bps.init()
    tx = optax.adam(1e-3)
    params = {"w": jnp.ones((3, 2))}
    st = tx.update(params, tx.init(params), params)[1]  # stepped state
    out = bps.broadcast_optimizer_state(st)
    flat1 = jax.tree_util.tree_leaves(st)
    flat2 = jax.tree_util.tree_leaves(out)
    assert len(flat1) == len(flat2)
    for a, b in zip(flat1, flat2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_timeline_window(tmp_path, monkeypatch):
    cfg = Config(trace_on=True, trace_dir=str(tmp_path / "tr"),
                 trace_start_step=2, trace_end_step=4)
    tl = Timeline(cfg, device_trace=False)
    assert not tl.active
    tl.step()            # step 1: before window
    assert not tl.active
    tl.step()            # step 2: window opens
    assert tl.active
    tl.step()            # step 3
    tl.step()            # step 4: dump + close
    assert not tl.active
    assert os.path.isdir(cfg.trace_dir)
    tl.step()            # past end: no-op
    tl.close()           # idempotent


@pytest.mark.parametrize("span_name", ["bps.ps.push_pull", "bps.step.ps"])
def test_timeline_combined_device_plus_dcn(tmp_path, span_name):
    """XPlane interop (SURVEY.md §5): the C core's DCN spans merge into
    the jax.profiler Chrome trace — device and host-comm stages on ONE
    timeline, core monotonic clock shifted onto the device timebase by the
    relation the capture itself carries (the mono_ns stat of a
    bps.ps.push_pull span, or of the bps.step.ps every PS step design
    writes, against its ts), not by the sampled anchor."""
    import json
    import time

    from byteps_tpu.jax import ps
    assert span_name in (ps.SPAN_PUSH_PULL, ps.SPAN_STEP_PS)
    from byteps_tpu.utils.timeline import (capture_clock_offset_us,
                                           find_device_chrome_trace,
                                           merge_core_device_traces)

    dev_dir = str(tmp_path / "dev")
    jax.profiler.start_trace(dev_dir)
    x = jax.jit(lambda a: a @ a)(jnp.ones((128, 128)))
    x.block_until_ready()
    span_mono_ns = time.monotonic_ns()
    with jax.profiler.TraceAnnotation(span_name, mono_ns=span_mono_ns):
        time.sleep(0.002)
    jax.profiler.stop_trace()
    assert find_device_chrome_trace(dev_dir) is not None

    # Synthetic C-core dump, stamped in the real monotonic clock exactly
    # as worker.cc::Record does: a push 500 us into the span.
    core_path = str(tmp_path / "comm.json")
    push_ts = span_mono_ns // 1000 + 500
    core = {"traceEvents": [
        {"name": "push", "ph": "X", "pid": 0, "tid": 7,
         "ts": push_ts, "dur": 1000, "args": {"key": 7}},
        {"name": "pull", "ph": "X", "pid": 0, "tid": 7,
         "ts": push_ts + 1000, "dur": 1500, "args": {"key": 7}},
    ]}
    with open(core_path, "w") as f:
        json.dump(core, f)

    out_path = str(tmp_path / "combined.json")
    # an anchor 10 s off: with the span in the capture it must not be used
    wrong_anchor = span_mono_ns // 1000 - 10_000_000
    n = merge_core_device_traces(core_path, dev_dir, out_path, wrong_anchor)
    assert n == 2
    with open(out_path) as f:
        merged = json.load(f)
    names = [e.get("name") for e in merged["traceEvents"]]
    assert "push" in names and "pull" in names
    # device events present too (far more than the 3 core+meta rows)
    assert len(merged["traceEvents"]) > 10
    dcn = [e for e in merged["traceEvents"] if e.get("name") == "push"][0]
    all_ts = [e["ts"] for e in merged["traceEvents"] if "ts" in e]
    # shifted onto the device timebase: within the trace's ts range,
    # not at raw monotonic magnitudes
    assert min(all_ts) - 1e6 < dcn["ts"] < max(all_ts) + 1e6
    # and exactly where it happened: 500 us after the span's start (the
    # stat is read a few us before the profiler stamps the span)
    span = [e for e in merged["traceEvents"]
            if e.get("name") == span_name][0]
    assert abs(dcn["ts"] - (span["ts"] + 500)) < 200
    # a capture without the span has no relation to offer: the anchor's turn
    assert capture_clock_offset_us([{"name": "push", "ts": 1.0}]) is None


def test_timeline_disabled():
    tl = Timeline(Config(trace_on=False), device_trace=False)
    for _ in range(5):
        tl.step()
    assert not tl.active


def test_callbacks_warmup_and_broadcast(rng):
    bps.init()
    state = {"params": {"w": jnp.ones((2, 2))}, "opt_state": None,
             "metrics": {"loss": 3.0}}
    cbs = CallbackList([
        BroadcastGlobalVariablesCallback(root_rank=0),
        MetricAverageCallback(),
        LearningRateWarmupCallback(initial_lr=0.1, multiplier=4.0,
                                   warmup_epochs=1, steps_per_epoch=10),
    ])
    cbs.on_train_begin(state)
    assert state["lr"] == 0.1
    for b in range(10):
        cbs.on_batch_end(b, state)
    assert abs(state["lr"] - 0.4) < 1e-9  # fully warmed: 0.1 * 4
    cbs.on_epoch_end(0, state)
    assert abs(state["metrics"]["loss"] - 3.0) < 1e-6  # collective mode: id


def test_warmup_schedule(rng):
    bps.init()
    sched = warmup_schedule(0.01, multiplier=8.0, warmup_steps=100)
    assert abs(float(sched(0)) - 0.01) < 1e-9
    assert abs(float(sched(100)) - 0.08) < 1e-7
    assert abs(float(sched(500)) - 0.08) < 1e-7
    mid = float(sched(50))
    assert 0.01 < mid < 0.08

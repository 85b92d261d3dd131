"""The PS leg's host spans (``byteps_tpu/jax/ps.py``): one table, mirrored by
the docs and the benchmark's readers, and a real PS step of each of the two
designs under a ``jax.profiler`` capture writes its part of it where the table
says."""

import json
import os
import re

import pytest

from benchmark.layers import psleg, roundbusy
from benchmark.lib import trace_reduce
from byteps_tpu.jax import ps
from tests.ps_utils import REPO, run_topology

WORKER = os.path.join(REPO, "tests", "_ps_spans_worker.py")
# the worker's tree: a 64x16, b 8, w 16x8, float32
LEAVES, BYTES = 3, 4 * (64 * 16 + 8 + 16 * 8)
TRACED = 2
# A CPU capture has no device plane and no line of programs: the reader's
# intervals come from the host spans alone, which is all that is asked here.
CPU = trace_reduce.Layout(device_plane=r"^/device:none$", op_lines=None,
                          sync_line=None, module_line="none")
# The bucketed step's spans of a traced step: one bps.ps.d2h / .stage a bucket
# (the worker asks for two), the rest once.
BUCKETED = {**dict.fromkeys(ps.SPANS[:3], 1), ps.SPAN_D2H: 2,
            ps.SPAN_STAGE: 2, ps.SPAN_WAIT: 1, ps.SPAN_H2D: 1}
# per case: the worker's builder, virtual devices, spans a traced step,
# partitions a round. Two devices are one controller's two chips: the step
# reduces in the program, so the host sees one tree a step and the counts are
# the one-device case's — once a step, not once a device.
DESIGNS = {
    "serial": ("serial", 1, dict.fromkeys(ps.SPANS, 1), LEAVES),
    "bucketed": ("bucketed", 1, BUCKETED, LEAVES),
    "bucketed-2dev": ("bucketed", 2, BUCKETED, LEAVES),
}
# benchmark/layers/psleg.py still reads this name as a second enqueue (the
# io_callback taps', which left in PR 60): read by the benchmark, written by
# no step. The one exception test_span_tables_agree allows.
READ_AND_NEVER_WRITTEN = {"bps.tap.push"}


def test_span_tables_agree():
    """The program's table of eight and the benchmark's mirror of it name
    the same spans; docs/timeline.md's table documents those, in the
    program's order; the reader of both designs names no span the program
    does not, but for the one name above."""
    from benchmark.layers import bridge

    assert len(ps.SPANS) == len(set(ps.SPANS)) == 8
    assert bridge.SPANS == ps.SPANS
    assert set(psleg.SPANS) - set(ps.SPANS) == READ_AND_NEVER_WRITTEN
    with open(os.path.join(REPO, "docs", "timeline.md")) as f:
        documented = re.findall(r"^\| `(bps\.[a-z0-9_.]+)` \|", f.read(), re.M)
    assert tuple(documented) == ps.SPANS


def _fleet(tmp_path, design="serial", **env):
    builder, devices = DESIGNS[design][:2]
    (out,) = run_topology(
        1, 1, WORKER, extra={
            "BYTEPS_PS_MODE": "ps", "BYTEPS_FORCE_DISTRIBUTED": "1",
            "BPS_SPANS_DIR": str(tmp_path / "trace"),
            "BPS_SPANS_BUILDER": builder,
            "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
            **env})
    return json.loads(out.strip().splitlines()[-1])


def _end(e):
    return e["start_ns"] + e["dur_ns"]


def _serial_bridge_spans(by_name, mono):
    """What only the serial step writes: push_pull and its four children
    together on one line (the bridge thread), the children inside it, in
    order, without overlap; the three stats on push_pull, with ``mono_ns``
    on the C core's clock, and ``stage_stats`` on stage."""
    for k, whole in enumerate(by_name[ps.SPAN_PUSH_PULL]):
        outer = by_name[ps.SPAN_STEP_PS][k]
        assert outer["start_ns"] <= whole["start_ns"] <= _end(whole) <= _end(
            outer)
        children = [by_name[n][k] for n in (
            ps.SPAN_D2H, ps.SPAN_STAGE, ps.SPAN_WAIT, ps.SPAN_H2D)]
        edges = [whole["start_ns"]]
        for child in children:
            edges += [child["start_ns"], _end(child)]
        edges.append(_end(whole))
        assert edges == sorted(edges)
        assert whole["stats"]["leaves"] == LEAVES
        assert whole["stats"]["bytes"] == BYTES
        lo, hi = mono
        assert lo < whole["stats"]["mono_ns"] < hi
        # bps.ps.stage carries stage_stats: a step before the capture left
        # its slots, all of which a device with memory of its own reuses
        # (the CPU backend may have made an upload of an aligned one)
        staged = children[1]["stats"]
        assert staged["bytes"] == whole["stats"]["bytes"]
        assert 0 <= staged["reused_bytes"] <= staged["bytes"]
        # float32 leaves, no codec: pushed from where they landed, all
        assert staged["direct_bytes"] == staged["bytes"]
    first, second = by_name[ps.SPAN_PUSH_PULL]
    # one clock relation for the whole capture: (mono_ns - ts) is a constant
    drift = ((second["stats"]["mono_ns"] - second["start_ns"])
             - (first["stats"]["mono_ns"] - first["start_ns"]))
    assert abs(drift) < 1_000_000
    return first


@pytest.mark.parametrize("design", list(DESIGNS))
def test_a_ps_step_writes_its_spans(tmp_path, design):
    """1 worker + 1 server on loopback, two traced steps of one design:
    every span of the design as many times a step as the table says and no
    other; the three step spans on the caller's line, the binding's on
    another (the bridge thread); ``bps.ps.wait`` inside ``bps.step.ps``;
    ``mono_ns`` on ``bps.step.ps``, on the C core's clock; a step's last
    ``bps.ps.stage`` has counted one gradient tree's bytes, however many
    chips the controller drives. The rounds the core has closed by then
    carry their stages and resources as elapsed time, and the first traced
    step's, put on the capture's clock through that ``mono_ns`` alone, lies
    inside the step's leg — first enqueue to the end of ``bps.ps.wait`` — to
    200 us."""
    builder, _, per_step, parts = DESIGNS[design]
    found = _fleet(tmp_path, design)
    events = found["events"]
    assert {e["plane"] for e in events} == {"/host:CPU"}
    assert {e["name"] for e in events} <= set(ps.SPANS)
    by_name = {name: [e for e in events if e["name"] == name]
               for name in ps.SPANS}
    assert {n: len(v) for n, v in by_name.items()} == {
        n: TRACED * per_step.get(n, 0) for n in ps.SPANS}

    caller = {e["line"] for n in ps.SPANS[:3] for e in by_name[n]}
    assert len(caller) == 1
    binding = {e["line"] for n in ps.SPANS[3:] for e in by_name[n]}
    assert len(binding) == 1 and binding != caller

    lo, hi = found["mono_ns"]
    for outer, wait in zip(by_name[ps.SPAN_STEP_PS], by_name[ps.SPAN_WAIT]):
        assert outer["start_ns"] <= wait["start_ns"] <= _end(wait) <= _end(
            outer)
        assert lo < outer["stats"]["mono_ns"] < hi
    # a round's pieces add to its first: the step's last stage span has the
    # whole tree, once
    stages = per_step[ps.SPAN_STAGE]
    assert [e["stats"]["bytes"] for e in by_name[ps.SPAN_STAGE][
        stages - 1::stages]] == [BYTES] * TRACED
    first_push_pull = (_serial_bridge_spans(by_name, found["mono_ns"])
                       if builder == "serial" else None)

    # three steps ran, so the core has closed the first two rounds: the
    # compiling step's and the first traced step's
    rounds = found["rounds"]
    assert [r["round"] for r in rounds] == [0, 1]
    for r in rounds:
        assert r["parts"] == parts
        for union, total in roundbusy.STAGES.values():
            assert 0 <= r[union] <= r["elapsed_us"], union
            if total:
                assert r[total] >= r[union], union
        assert 0 <= r["feed_wait_us"] <= r["elapsed_us"]
        assert r["server_span_us"] <= r["push_span_us"]
        assert r["server_us"] <= r["push_us"]
        # what a few partitions over loopback cannot do in no time at all
        for name in ("push_span_us", "pull_span_us", "server_us",
                     "push_thread_us", "send_blocked_us", "recv_thread_us"):
            assert r[name] > 0, name

    # round 1 is the first traced step's. The benchmark's reader cuts the
    # capture into steps and legs; through bps.step.ps's mono_ns the round
    # lies inside its step's leg.
    steps = psleg.split_steps(
        [(e["plane"], str(e["line"]), e["name"], e["start_ns"], e["dur_ns"])
         for e in events], CPU)
    assert len(steps) == TRACED
    for step in steps:
        assert step["enqueues"] == per_step[ps.SPAN_STAGE]
        assert step["hidden"] == 0 and step["exposed"] == step["leg"] > 0
    (row,) = psleg.align(rounds[1:], steps, [
        (e["start_ns"], e["stats"]["mono_ns"])
        for e in by_name[ps.SPAN_STEP_PS]])
    assert row["round"] == 1
    assert row["start_margin_ms"] >= -0.2 and row["end_margin_ms"] >= -0.2
    # the binding's steps enqueue inside bps.step.ps
    assert row["step_ps_start_margin_ms"] >= -0.2
    assert row["step_ps_end_margin_ms"] >= -0.2
    if first_push_pull:
        # and, as before bps.step.ps carried the anchor, inside push_pull
        (row,) = roundbusy.align(rounds[1:], [
            (first_push_pull["start_ns"], first_push_pull["dur_ns"],
             first_push_pull["stats"]["mono_ns"])])
        assert row["round"] == 1
        assert row["start_margin_ms"] >= 0 and row["end_margin_ms"] >= 0


@pytest.mark.parametrize("worker_on, server_on", [("1", "0"), ("0", "1")])
def test_rounds_complete_against_a_peer_without_the_stamps(
        tmp_path, worker_on, server_on):
    """A server that sends 0 where the ack carries its times (one with the
    stamps off does, as one from before them), and a worker that reads
    nothing there: the steps complete either way, and the worker that looks
    reports ``server_us`` 0 — all wire."""
    found = _fleet(tmp_path, BYTEPS_ROUNDSTATS_ON=server_on,
                   BPS_SPANS_WORKER_ROUNDSTATS=worker_on)
    assert len([e for e in found["events"]
                if e["name"] == ps.SPAN_PUSH_PULL]) == 2
    if worker_on == "0":
        assert found["rounds"] == []
        return
    assert [r["round"] for r in found["rounds"]] == [0, 1]
    for r in found["rounds"]:
        assert r["server_us"] == r["server_span_us"] == r["sum_us"] == 0
        assert r["push_us"] > 0 and r["wire_ack_us"] == r["push_us"]

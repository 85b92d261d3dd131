"""The PS leg's host spans (``byteps_tpu/jax/ps.py::SPANS``): one table,
mirrored by the docs and the benchmark's reader, and a real PS step under a
``jax.profiler`` capture writes all eight where the table says."""

import json
import os
import re

import pytest

from benchmark.layers import roundbusy
from byteps_tpu.jax import ps
from tests.ps_utils import REPO, run_topology

WORKER = os.path.join(REPO, "tests", "_ps_spans_worker.py")


def test_span_tables_agree():
    """The program's table, docs/timeline.md's and the benchmark reader's
    mirror name the same eight spans."""
    from benchmark.layers import bridge

    assert len(ps.SPANS) == len(set(ps.SPANS)) == 8
    assert bridge.SPANS == ps.SPANS
    with open(os.path.join(REPO, "docs", "timeline.md")) as f:
        documented = re.findall(r"^\| `(bps\.[a-z0-9_.]+)` \|", f.read(), re.M)
    assert tuple(documented) == ps.SPANS


def _fleet(tmp_path, **env):
    (out,) = run_topology(
        1, 1, WORKER, extra={
            "BYTEPS_PS_MODE": "ps", "BYTEPS_FORCE_DISTRIBUTED": "1",
            "BPS_SPANS_DIR": str(tmp_path / "trace"),
            "XLA_FLAGS": "--xla_force_host_platform_device_count=1", **env})
    return json.loads(out.strip().splitlines()[-1])


def test_a_ps_step_writes_the_eight_spans(tmp_path):
    """1 worker + 1 server on loopback, two traced steps: every span twice;
    the three step spans on the caller's line; push_pull and its five
    children together on another (the bridge thread), the children inside
    it, in order, without overlap; the three stats on push_pull, with
    ``mono_ns`` on the C core's clock, and ``stage_stats`` on stage. The two
    rounds the core has closed by then carry their stages and resources as
    elapsed time, and each lies inside its step's push_pull span."""
    found = _fleet(tmp_path)
    events = found["events"]
    assert {e["plane"] for e in events} == {"/host:CPU"}
    by_name = {name: [e for e in events if e["name"] == name]
               for name in ps.SPANS}
    assert {n: len(v) for n, v in by_name.items()} == dict.fromkeys(
        ps.SPANS, 2)

    caller = {e["line"] for n in ps.SPANS[:3] for e in by_name[n]}
    bridge = {e["line"] for n in ps.SPANS[3:] for e in by_name[n]}
    assert len(caller) == 1 and len(bridge) == 1 and caller != bridge

    def end(e):
        return e["start_ns"] + e["dur_ns"]

    for k, whole in enumerate(by_name[ps.SPAN_PUSH_PULL]):
        outer = by_name[ps.SPAN_STEP_PS][k]
        assert outer["start_ns"] <= whole["start_ns"] <= end(whole) <= end(
            outer)
        children = [by_name[n][k] for n in (
            ps.SPAN_D2H, ps.SPAN_STAGE, ps.SPAN_WAIT, ps.SPAN_H2D)]
        edges = [whole["start_ns"]]
        for child in children:
            edges += [child["start_ns"], end(child)]
        edges.append(end(whole))
        assert edges == sorted(edges)
        # 2 leaves: w 64x8 and b 8, float32
        assert whole["stats"]["leaves"] == 2
        assert whole["stats"]["bytes"] == 4 * (64 * 8 + 8)
        lo, hi = found["mono_ns"]
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

    # three steps ran, so the core has closed the first two rounds: the
    # compiling step's and the first traced step's
    rounds = found["rounds"]
    assert [r["round"] for r in rounds] == [0, 1]
    for r in rounds:
        assert r["parts"] == 2
        for union, total in roundbusy.STAGES.values():
            assert 0 <= r[union] <= r["elapsed_us"], union
            if total:
                assert r[total] >= r[union], union
        assert 0 <= r["feed_wait_us"] <= r["elapsed_us"]
        assert r["server_span_us"] <= r["push_span_us"]
        assert r["server_us"] <= r["push_us"]
        # what two partitions over loopback TCP cannot do in no time at all
        for name in ("push_span_us", "pull_span_us", "server_us",
                     "push_thread_us", "send_blocked_us", "recv_thread_us"):
            assert r[name] > 0, name
    # round 1 is the first traced step's: through mono_ns its ends lie
    # inside that step's bps.ps.push_pull
    (row,) = roundbusy.align(rounds[1:], [
        (first["start_ns"], first["dur_ns"], first["stats"]["mono_ns"])])
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

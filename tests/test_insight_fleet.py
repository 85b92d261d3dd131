"""Per-round introspection fleet tests (ISSUE 7 acceptance, ps tier).

Real 2-worker topologies:

- the scheduler's fleet round table (heartbeat-piggybacked summaries)
  must hold EVERY completed round for every worker and match each
  worker's own /metrics round gauges exactly once the rounds align;
- a deliberately wire-starved run (fusion off, sub-64KB keys) must
  classify ``wire-bound``;
- a pacing-throttled worker must flip the fleet state to
  ``straggler-skewed``;
- a quant-on chaos run (drop/dup, seed 42) must complete bit-identical
  to the fault-free quant run with summaries still flowing (PR 3/6
  composition — heartbeats are control-plane, chaos never touches
  them).
"""

import json
import os
import time
import urllib.request

import pytest

from byteps_tpu.monitor import insight
from tests.ps_utils import free_port, run_topology, spawn_role, \
    spawn_worker, topology_env

WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "_ps_worker.py")


def _free_port_block(n: int) -> int:
    import random
    import socket

    rng = random.Random()
    for _ in range(50):
        base = rng.randrange(20000, 55000)
        socks = []
        try:
            for i in range(n):
                s = socket.socket()
                s.bind(("127.0.0.1", base + i))
                socks.append(s)
            return base
        except OSError:
            continue
        finally:
            for s in socks:
                s.close()
    raise RuntimeError("no free port block found")


def _scrape_rounds(port: int, timeout: float = 5.0) -> dict:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/rounds",
                                timeout=timeout) as r:
        return json.loads(r.read().decode())


def _run_insight_fleet(workers, servers, extra, worker_extras=None,
                       rounds=6):
    """Spawn an insight_hold fleet; returns (scheduler summary, per-
    worker JSON records, cleanup-and-assert function already run)."""
    base = _free_port_block(1 + servers + workers)
    port = free_port()
    go_file = extra.pop("_go_file")
    env = topology_env(workers, servers, port, {
        "BYTEPS_MONITOR_ON": "1",
        "BYTEPS_MONITOR_PORT": str(base),
        "BPS_TEST_GO_FILE": go_file,
        "BPS_TEST_INSIGHT_ROUNDS": str(rounds),
        **extra,
    })
    procs = [("scheduler", spawn_role("scheduler", env))]
    for _ in range(servers):
        procs.append(("server", spawn_role("server", env)))
    wprocs = []
    for r in range(workers):
        wx = (worker_extras or {}).get(r, {})
        p = spawn_worker(WORKER, env, r, "insight_hold", extra=wx)
        procs.append((f"worker{r}", p))
        wprocs.append(p)
    records = []
    summary = None
    try:
        for p in wprocs:
            rec = None
            for line in p.stdout:
                if line.startswith("{"):
                    rec = json.loads(line)
                if line.startswith("ready"):
                    break
            assert rec is not None, "worker printed no record"
            records.append(rec)
        # Poll the scheduler until every worker's LAST completed round
        # (rounds-1; the sentinel closed it) arrived via heartbeats.
        want_last = rounds - 1
        deadline = time.time() + 20
        while time.time() < deadline:
            summary = _scrape_rounds(base)
            fleet_workers = {n: st for n, st in summary["fleet"].items()
                             if st.get("role") == 2}
            if (len(fleet_workers) == workers
                    and all(st["last"]["round"] >= want_last
                            for st in fleet_workers.values())):
                break
            time.sleep(0.5)
    finally:
        with open(go_file, "w") as f:
            f.write("go")
        fails = []
        for name, p in procs:
            try:
                out, _ = p.communicate(timeout=90)
            except Exception:
                p.kill()
                out, _ = p.communicate()
            if p.returncode != 0:
                fails.append((name, p.returncode, out))
        assert not fails, "\n".join(
            f"--- {n} exited {rc} ---\n{out}" for n, rc, out in fails)
    return summary, records


@pytest.mark.ps
def test_scheduler_round_table_matches_workers_and_wire_bound(tmp_path):
    """2w x 2s comm-only, fusion OFF over sub-64KB keys (the wire-
    starved shape): the scheduler shows summaries for EVERY completed
    round of both workers, each matching the worker's own /metrics
    gauges exactly, and insight classifies the fleet wire-bound."""
    rounds = 6
    summary, records = _run_insight_fleet(
        2, 2,
        {"_go_file": str(tmp_path / "go"),
         "BYTEPS_FUSION_BYTES": "0",       # every tiny key = own frame
         "BPS_TEST_INSIGHT_N": "2048",     # 8 KiB keys, sub-64KB
         "BPS_TEST_INSIGHT_KEYS": "24",
         "BYTEPS_TRACE_DIR": str(tmp_path / "traces")},
        # Worker 0 also proves the flight-dump rename (ISSUE 7
        # satellite): its pre-init pid-named dump must become
        # flight_r2_n<id>.json once the topology assigns its id.
        worker_extras={0: {"BPS_TEST_PREINIT_FLIGHT": "1"}},
        rounds=rounds)
    assert summary is not None
    fleet = {n: st for n, st in summary["fleet"].items()
             if st.get("role") == 2}
    assert len(fleet) == 2, summary["fleet"].keys()

    # Every completed round of every worker is in the fleet table.
    table = summary["fleet_rounds"]
    for rnd in range(rounds):
        assert str(rnd) in table, (rnd, sorted(table))
        for node in fleet:
            assert node in table[str(rnd)], (rnd, node)
    # Per-round parts = keys (each key is one partition here).
    for rnd in range(rounds):
        for node in fleet:
            assert table[str(rnd)][node]["parts"] == 24

    # The scheduler's record for a worker's last round IS the record
    # the worker holds locally (bit-for-bit: same C struct, two paths).
    for rec in records:
        node = str(rec["node_id"])
        local_last = rec["local_last"]
        sched_rec = table[str(local_last["round"])][node]
        # ... plus, locally only, the round's elapsed-time stamps and its
        # stages and resources as elapsed time: they sit beside the wire
        # struct and never cross the heartbeat.
        assert set(local_last) - set(sched_rec) == {
            "start_us", "elapsed_us", "push_offset_us", "push_window_us",
            "pull_offset_us", "pull_window_us", "queue_span_us",
            "comp_span_us", "push_span_us", "sum_span_us", "pull_span_us",
            "dec_span_us", "feed_wait_us", "credit_blocked_us",
            "push_thread_us", "push_thread_sum_us", "send_blocked_us",
            "send_blocked_sum_us", "server_us", "server_span_us",
            "recv_thread_us", "recv_thread_sum_us", "van_recv_us"}
        assert sched_rec == {k: local_last[k] for k in sched_rec}, (
            sched_rec, local_last)
        # /metrics gauges mirror the same record (monitor.top's view).
        g = rec["gauges"]
        assert g["bps_round_last"] == local_last["round"]
        assert g["bps_round_parts"] == local_last["parts"]
        assert g["bps_round_push_us"] == local_last["push_us"]
        assert g["bps_round_sum_us"] == local_last["sum_us"]
        assert g["bps_round_wire_bytes"] == local_last["wire_bytes"]
        assert rec["rounds_completed"] >= rounds

    # Wire-starved classification: per-message overhead dominates (no
    # fusion, tiny keys), so wire_ack owns the round. Classified over a
    # 3-round window — a single round's record is pacing-sensitive
    # under parallel suite load (one scheduler hiccup on one worker
    # reads as straggler skew); the window averages it out (ISSUE 9
    # deflake satellite).
    rep = insight.analyze(summary, window=3)
    assert rep["state"] == "wire-bound", rep
    # A wire-bound fleet with zero fused frames names the fusion knob.
    assert any("BYTEPS_FUSION_BYTES" in h for h in rep["hints"]), rep

    # Server-side sum time flows back through acks: with real tensors
    # the per-round sum cannot be literally zero on every round.
    assert any(table[str(r)][n]["sum_us"] > 0
               for r in range(rounds) for n in fleet)


@pytest.mark.ps
def test_paced_straggler_flips_fleet_state(tmp_path):
    """One pacing-throttled worker (2 MB/s against 1 MB pushes): its
    per-round push wall inflates ~3 orders of magnitude, and the fleet
    classifies straggler-skewed — not merely wire-bound."""
    rounds = 4
    summary, records = _run_insight_fleet(
        2, 1,
        {"_go_file": str(tmp_path / "go"),
         "BPS_TEST_INSIGHT_N": str(1 << 18),   # 1 MB float32 keys
         "BPS_TEST_INSIGHT_KEYS": "2",
         # The paced worker's ~0.5 s/MB pushes legitimately graze the
         # default 1 s retry clock; a resend would flip the (higher-
         # precedence) retry-degraded state and hide the skew this
         # test is about. Pacing is slowness, not loss — no retries.
         "BYTEPS_RETRY_TIMEOUT_MS": "8000"},
        worker_extras={1: {"BYTEPS_PACING_RATE": "2000000"}},
        rounds=rounds)
    assert summary is not None
    # Classify over a completed-round WINDOW, not one round: a single
    # record is pacing-sensitive under parallel suite load (one
    # scheduler hiccup on the un-paced worker flips its ratios and the
    # run flaked); summing the last 3 rounds classifies the same share
    # arithmetic over a stable base (ISSUE 9 deflake satellite).
    rep = insight.analyze(summary, window=3)
    assert rep["state"] == "straggler-skewed", rep
    assert len(rep["stragglers"]) == 1, rep
    # The straggler is the paced worker: its push wall dwarfs the
    # peer's — compared over the same window, not one round.
    recs = insight.window_recs(summary, 3)
    walls = {n: insight.stage_breakdown(r)["wire_ack"]
             for n, r in recs.items()}
    straggler = rep["stragglers"][0]
    other = next(n for n in walls if n != straggler)
    assert walls[straggler] > 5 * walls[other], walls


@pytest.mark.ps
@pytest.mark.quant
def test_quant_chaos_bit_identical_with_summaries_flowing():
    """Composition acceptance: quant-on chaos (drop/dup seed 42) must
    reproduce the fault-free quant digest bitwise, with round
    summaries still reaching the scheduler mid-fault (heartbeats are
    control-plane: the chaos layer provably never injects them)."""
    def run(chaos: bool):
        base = _free_port_block(5)
        extra = {
            "BYTEPS_WIRE_QUANT": "1",
            "BYTEPS_MONITOR_ON": "1",
            "BYTEPS_MONITOR_PORT": str(base),
        }
        if chaos:
            extra.update({
                "BYTEPS_CHAOS_SEED": "42",
                "BYTEPS_CHAOS_DROP": "0.03",
                "BYTEPS_CHAOS_DUP": "0.03",
            })
        outs = run_topology(2, 2, WORKER, mode="quant", extra=extra,
                            timeout=180)
        recs = []
        for out in outs:
            line = [ln for ln in out.splitlines()
                    if ln.startswith("{")][-1]
            recs.append(json.loads(line))
        return recs

    clean = run(chaos=False)
    chaotic = run(chaos=True)
    assert sorted(r["digest"] for r in clean) == \
        sorted(r["digest"] for r in chaotic), \
        "quant+chaos diverged from the fault-free quant run"
    # Chaos provably armed, absorbed in-band.
    assert sum(r["chaos_injected"] for r in chaotic) > 0
    assert sum(r["retries"] for r in chaotic) > 0
    # Summaries flowed on every worker AND reached the scheduler's
    # fleet table during the chaotic run (rank 0 polls /rounds).
    for r in chaotic:
        assert r["rounds_completed"] > 0, r
    rank0 = [r for r in chaotic if r["sched_fleet_workers"] is not None]
    assert rank0 and rank0[0]["sched_fleet_workers"] == 2, chaotic

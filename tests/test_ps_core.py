"""C++ core integration tests: real localhost PS topology, no mocks.

Covers the reference test matrix (SURVEY.md §4): push_pull numerics over
shapes/dtypes/rounds, averaging, multi-partition multi-server tensors,
broadcast from root, handle semantics, compression codecs + error
feedback, async mode, trace timeline, barriers.
"""

import json
import os

import pytest

from tests.ps_utils import TCP, assert_transport, run_topology

WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "_ps_worker.py")

pytestmark = pytest.mark.ps  # slow-ish multiprocess tests

# The van derives a connection's transport (ISSUE 38): on this sandbox
# every peer is local, so a fleet that says nothing runs on shm rings.
# The tests that mean the TCP wire — the one a remote peer gets — say so.
# What the van derives, and a refused offer, are tier-1's
# tests/test_van_transport.py.
TRANSPORTS = [("tcp", TCP), ("shm", {"BYTEPS_VAN_TYPE": "shm"})]


def test_basic_sum_2workers_1server():
    run_topology(2, 1, WORKER, mode="basic")


def test_basic_sum_3workers_2servers():
    run_topology(3, 2, WORKER, mode="basic")


def test_average():
    run_topology(2, 1, WORKER, mode="average")


def test_multipartition_spans_servers():
    run_topology(2, 3, WORKER, mode="multipart",
                 extra={"BYTEPS_PARTITION_BYTES": "65536"})


def test_broadcast_from_root():
    run_topology(3, 2, WORKER, mode="broadcast")


@pytest.mark.parametrize("rate,mode,floor_s", [
    (1_000_000_000, "basic", 0.0),
    (2_000_000, "multipart", 0.5),
], ids=["generous", "holds"])
def test_pacing_rate_path(rate, mode, floor_s):
    """BYTEPS_PACING_RATE (kernel TCP pacing — the production
    NIC-fair-share knob) must leave numerics intact, and a connection
    that asked for it keeps its socket: a ring cannot pace, so no ring is
    offered to the local peer (transport asserted from the counters and
    the DEBUG line). `generous` costs no wall time; `holds` moves 1.2 MB
    each way at 2 MB/s and must take the time the pace says (0.6 s a
    direction; over a ring it would take milliseconds)."""
    outs = run_topology(2, 1, WORKER, mode=mode,
                        extra={"BYTEPS_PACING_RATE": str(rate),
                               "BYTEPS_PARTITION_BYTES": "65536",
                               "BYTEPS_LOG_LEVEL": "DEBUG"}, timeout=120.0)
    assert_transport(outs, "tcp", dialled=2)  # scheduler + server
    for o in outs:
        assert "BYTEPS_PACING_RATE paces the socket" in o, o[-2000:]
        if floor_s:
            took = float([l for l in o.splitlines()
                          if l.startswith("push_pull_s ")][0].split()[1])
            assert took >= floor_s, (took, o[-2000:])


def test_rebroadcast_delivers_fresh_values():
    run_topology(3, 2, WORKER, mode="rebroadcast")


def test_multiple_inflight_handles():
    run_topology(2, 2, WORKER, mode="handles",
                 # byte budget = two of the 16 KiB test tensors in flight
                 extra={"BYTEPS_SCHEDULING_CREDIT": "32768"})


def test_byte_credit_bounds_inflight(tmp_path):
    """BYTEPS_SCHEDULING_CREDIT is a BYTE budget (reference semantics): a
    16-partition tensor under a 2-partition byte budget never holds more
    than 2 partitions in flight, and a later-declared small tensor still
    completes (VERDICT r1 weak #8)."""
    run_topology(2, 1, WORKER, mode="byte_credit",
                 extra={"BYTEPS_PARTITION_BYTES": "65536",
                        "BYTEPS_SCHEDULING_CREDIT": "131072",
                        "BYTEPS_TRACE_ON": "1",
                        "BYTEPS_TRACE_DIR": str(tmp_path)})


_PART = 65536


@pytest.mark.parametrize("extra,budget", [
    ({}, 10 * _PART),
    (TCP, 10 * _PART),
    ({"BYTEPS_SCHEDULING_CREDIT": "196608"}, 196608),
    ({"BYTEPS_SCHEDULING_CREDIT": "200000"}, 200000),
    ({"BYTEPS_SCHEDULING_CREDIT": "3"}, 3 * _PART),
    ({"BYTEPS_SCHEDULING_CREDIT": "32768"}, 32768),
    ({"BYTEPS_SCHEDULING_CREDIT": str(64 * _PART)}, 64 * _PART),
], ids=["default", "default-tcp", "forced", "forced-odd", "legacy-count",
        "under-a-partition", "over-the-round"])
def test_credit_budget_on_a_fleet(extra, budget):
    """ISSUE 48: unset, the budget is ten partitions' worth; a forced
    BYTEPS_SCHEDULING_CREDIT is honoured to the byte. With the server
    unable to answer (one worker pushes late) exactly the whole partitions
    the budget admits stand in flight — one when the budget is under a
    partition, the whole round when it is over it — and the sums stay
    exact."""
    outs = run_topology(2, 1, WORKER, mode="credit_budget",
                        extra={"BYTEPS_PARTITION_BYTES": str(_PART), **extra})
    for o in outs:
        assert f"credit_budget {budget}" in o, o[-2000:]


def test_priority_preemption(tmp_path):
    """Declaration-order priority (the reference's front-of-model-first
    scheduling): across repeated rounds under a 1-partition byte budget,
    the earlier-declared tensor pops ahead of a later-declared tensor
    that entered the queue first — a pop order FIFO cannot produce."""
    run_topology(1, 1, WORKER, mode="priority",
                 extra={"BYTEPS_PARTITION_BYTES": "65536",
                        "BYTEPS_SCHEDULING_CREDIT": "65536",
                        "BYTEPS_FORCE_DISTRIBUTED": "1",
                        "BYTEPS_TRACE_ON": "1",
                        "BYTEPS_TRACE_DIR": str(tmp_path)})


def test_fifo_mode_disables_preemption(tmp_path):
    """BYTEPS_SCHEDULING=fifo (the A/B switch against declaration-order
    priority): the priority signature — an earlier-declared tensor
    popping ahead of a later-declared one that entered the queue first —
    must NEVER appear."""
    run_topology(1, 1, WORKER, mode="priority",
                 extra={"BYTEPS_PARTITION_BYTES": "65536",
                        "BYTEPS_SCHEDULING_CREDIT": "65536",
                        "BYTEPS_SCHEDULING": "fifo",
                        "BYTEPS_FORCE_DISTRIBUTED": "1",
                        "BYTEPS_TRACE_ON": "1",
                        "BYTEPS_TRACE_DIR": str(tmp_path)})


def test_deep_pipelining_one_tensor():
    """3+ rounds of one tensor in flight: the server must park (not
    fail-stop on) pushes for a round whose slot is still busy, and every
    round's aggregate must stay exact (VERDICT r1 weak #4)."""
    run_topology(2, 1, WORKER, mode="deep_pipeline")


def test_fleet_outlives_finalize_grace():
    """A fleet must serve for the whole job, not a bounded grace window:
    the server/scheduler entry calls shutdown() at startup, so their
    Finalize wait IS the serving loop. Worker idles 35 s (past the old
    30 s bound) before its first push; the push must still aggregate."""
    run_topology(2, 1, WORKER, mode="slow_job", timeout=120.0)


@pytest.mark.parametrize("name,transport", TRANSPORTS,
                         ids=[t[0] for t in TRANSPORTS])
def test_no_recv_thread_send_deadlock(name, transport):
    """Sustained multi-round MB-scale traffic over tiny (64 KiB) kernel
    socket buffers, or rings as small: response callbacks must run off
    the van recv threads (key-hashed executor), else the push->pull
    chain's send from the recv thread wedges both directions once the
    buffers fill."""
    run_topology(2, 1, WORKER, mode="congested",
                 extra={"BYTEPS_SOCKET_BUF": "65536",
                        "BYTEPS_SHM_RING_BYTES": "65536", **transport},
                 timeout=180.0)


@pytest.mark.parametrize("name,transport", TRANSPORTS,
                         ids=[t[0] for t in TRANSPORTS])
def test_van_striped_streams(name, transport):
    """BYTEPS_VAN_STREAMS=4: each worker dials 4 striped connections per
    server (each its own socket, or its own ring); keys hash onto streams
    (per-key ordering preserved). The multi-round MB-scale workload must
    aggregate exactly, as with one stream."""
    outs = run_topology(2, 1, WORKER, mode="congested",
                        extra={"BYTEPS_VAN_STREAMS": "4",
                               "BYTEPS_LOG_LEVEL": "DEBUG", **transport},
                        timeout=180.0)
    assert_transport(outs, name, dialled=5)  # scheduler + 4 stripes


def test_van_shm_transport():
    """BYTEPS_VAN_TYPE=shm (second van transport, the reference's
    ZMQVan-ipc:///rdma_van role — SURVEY.md §2.4): loopback connections
    negotiate per-connection shared-memory rings over CMD_SHM_HELLO and
    every frame moves through them. The sustained multi-round MB-scale
    workload must aggregate exactly, as over TCP."""
    run_topology(2, 1, WORKER, mode="congested",
                 extra={"BYTEPS_VAN_TYPE": "shm"}, timeout=180.0)


def test_van_shm_tiny_ring_streams_large_frames():
    """Frames larger than the ring must stream through it like a socket
    buffer (producer chunks, consumer drains concurrently): MB-scale
    messages over 64 KiB rings."""
    run_topology(2, 1, WORKER, mode="congested",
                 extra={"BYTEPS_VAN_TYPE": "shm",
                        "BYTEPS_SHM_RING_BYTES": "65536"}, timeout=180.0)


def _run_dead_server_fast_fail(extra_env):
    """Kill the only server once the worker is mid-flight; the worker's
    peer-lost hook must fail the handle in seconds (not the 30 s
    heartbeat detector) and the worker script reports fast-fail OK.

    Hot server replacement is explicitly DISABLED here: with it on (the
    default) a dead server parks its requests awaiting a replacement
    instead of fast-failing — that path is covered by test_recovery.py;
    this helper pins the recovery-off fail-fast contract."""
    from tests.ps_utils import free_port, spawn_role, spawn_worker, \
        topology_env

    merged = {"BYTEPS_RECOVERY_TIMEOUT_MS": "0"}
    merged.update(extra_env or {})
    port = free_port()
    env = topology_env(1, 1, port, merged)
    sched = spawn_role("scheduler", env)
    server = spawn_role("server", env)
    worker = spawn_worker(WORKER, env, 0, "fast_fail")
    try:
        for line in worker.stdout:
            if line.startswith("ready"):
                break
        server.kill()
        out, _ = worker.communicate(timeout=30)
        assert worker.returncode == 0, out
        assert "fast-fail OK" in out, out
    finally:
        for p in (sched, server, worker):
            if p.poll() is None:
                p.kill()
                p.communicate()


def test_van_shm_dead_server_fast_fail():
    """Peer-death detection on the shm transport: the TCP socket idles
    under the rings precisely so a killed server still surfaces as an EOF
    — fast-fail must work unchanged (no heartbeat wait)."""
    _run_dead_server_fast_fail({"BYTEPS_VAN_TYPE": "shm"})


def test_van_shm_engages_on_non_loopback_local_address():
    """The shm decision is by RESOLVED address vs local interfaces, not
    literal '127.0.0.1' (docs promise 'a co-located worker/server pair
    in any deployment'): a fleet addressing itself by the host's real IP
    (DMLC_NODE_HOST in a mixed deployment) must still negotiate rings —
    asserted from the van's own DEBUG line, so a silent TCP fallback
    fails the test rather than passing it."""
    import subprocess

    ip = subprocess.run(["hostname", "-I"], capture_output=True,
                        text=True).stdout.split()
    ip = next((a for a in ip if "." in a and not a.startswith("127.")),
              None)
    if ip is None:
        pytest.skip("host has no non-loopback IPv4 address")
    outs = run_topology(1, 1, WORKER, mode="basic",
                        extra={"BYTEPS_VAN_TYPE": "shm",
                               "DMLC_PS_ROOT_URI": ip,
                               "DMLC_NODE_HOST": ip,
                               "BYTEPS_LOG_LEVEL": "DEBUG"})
    assert any("shm ring" in o for o in outs), outs[0][-2000:]


def test_onebit_semantics():
    run_topology(1, 1, WORKER, mode="onebit",
                 extra={"BYTEPS_FORCE_DISTRIBUTED": "1"})


def test_topk_lossless_aggregation():
    run_topology(2, 1, WORKER, mode="topk_lossless")


def test_pull_leg_compression_bytes_drop():
    """Server symmetry (SURVEY.md §2.2): pull responses are re-encoded
    with the key's codec, so DCN bytes drop in BOTH directions for
    type=onebit (VERDICT r1 missing #1)."""
    run_topology(2, 1, WORKER, mode="pull_compress")


def test_error_feedback_converges():
    run_topology(1, 1, WORKER, mode="error_feedback")


def test_async_mode():
    run_topology(2, 1, WORKER, mode="async",
                 extra={"BYTEPS_ENABLE_ASYNC": "1"})


def _run_fusion_topology(fusion_bytes: int, streams: int = 0):
    """One 2-worker x 2-server many-small-tensor run; returns the workers'
    result rows (digest + wire counters; parity asserted in-worker)."""
    import json
    import random
    import socket

    # A base port with 5 consecutive free ports (scheduler + 2 servers +
    # 2 workers serve /metrics on base + node_id).
    rng = random.Random()
    base = None
    for _ in range(50):
        cand = rng.randrange(20000, 55000)
        socks = []
        try:
            for i in range(5):
                s = socket.socket()
                s.bind(("127.0.0.1", cand + i))
                socks.append(s)
            base = cand
            break
        except OSError:
            continue
        finally:
            for s in socks:
                s.close()
    assert base is not None, "no free port block found"
    extra = {"BYTEPS_FUSION_BYTES": str(fusion_bytes),
             "BYTEPS_MONITOR_ON": "1",
             "BYTEPS_MONITOR_PORT": str(base)}
    if streams:
        extra["BYTEPS_VAN_STREAMS"] = str(streams)
    outs = run_topology(2, 2, WORKER, mode="fusion", extra=extra)
    rows = [json.loads(ln) for o in outs for ln in o.splitlines()
            if ln.startswith("{")]
    assert len(rows) == 2, outs
    return rows


def test_fusion_on_off_bit_identical_and_fewer_frames():
    """Small-tensor fusion acceptance (ISSUE 2): on a many-small-tensor
    workload over 2 workers x 2 servers, fusion on vs off must produce
    BIT-IDENTICAL aggregates (exact integer-valued floats, digests
    compared across runs), a monotone wire-frame reduction (scraped via
    bps_fused_msgs_total / bps_van_sent_frames_total), and the
    worker/server push-byte parity contract must hold under fusion
    (asserted in-worker over real /metrics scrapes)."""
    on = _run_fusion_topology(65536)
    off = _run_fusion_topology(0)
    # Same aggregates, bit for bit, on every worker in both runs.
    digests = {r["digest"] for r in on} | {r["digest"] for r in off}
    assert len(digests) == 1, (on, off)
    # Fusion off is the pre-fusion wire protocol: zero fused frames.
    assert all(r["fused"] == 0 for r in off), off
    # Fusion on actually fused, covered every partition exactly once,
    # and cut the wire message count.
    assert all(r["fused"] > 0 for r in on), on
    assert (sum(r["push_partitions"] for r in on)
            == sum(r["push_partitions"] for r in off)), (on, off)
    assert all(r["push_bytes"] == roff["push_bytes"]
               for r, roff in zip(on, off)), (on, off)
    frames_on = sum(r["frames"] for r in on)
    frames_off = sum(r["frames"] for r in off)
    assert frames_on < frames_off, (frames_on, frames_off)


def test_fusion_under_striping():
    """Fusion + BYTEPS_VAN_STREAMS (REVIEW: stripe-routing hazard): the
    collector batches per (server, stripe), so every key in a fused frame
    rides the striped connection its own hash picks — a key's chain must
    never hop stripes depending on batch composition. Fusion must still
    engage and produce aggregates bit-identical to the unfused wire with
    the same stripe count."""
    on = _run_fusion_topology(65536, streams=2)
    off = _run_fusion_topology(0, streams=2)
    digests = {r["digest"] for r in on} | {r["digest"] for r in off}
    assert len(digests) == 1, (on, off)
    assert all(r["fused"] == 0 for r in off), off
    assert all(r["fused"] > 0 for r in on), on
    assert all(r["push_bytes"] == roff["push_bytes"]
               for r, roff in zip(on, off)), (on, off)


def test_fusion_deep_pipeline_parked_acks():
    """Fused frames whose sub-pushes PARK server-side (REVIEW:
    batched-ack deadlock): deep-pipelined small tensors force parked
    sub-pushes inside mixed-round fused frames across two workers; the
    server must ack a parking sub-push at park time — withholding the
    batched ack until the slot recycles can deadlock the fleet (this
    test then times out). Aggregates must stay exact."""
    run_topology(2, 1, WORKER, mode="fusion_pipeline",
                 extra={"BYTEPS_FUSION_BYTES": "65536"})


def test_trace_timeline(tmp_path):
    # Deliberately uses the LEGACY BPS_TRACE_OUT alias: it must keep
    # working end-to-end (BYTEPS_TRACE_DIR is canonical; ISSUE 5).
    run_topology(1, 1, WORKER, mode="trace",
                 extra={"BYTEPS_TRACE_ON": "1",
                        "BPS_TRACE_OUT": str(tmp_path),
                        "BYTEPS_PARTITION_BYTES": "65536"})


def test_barrier():
    run_topology(3, 1, WORKER, mode="barrier")


def test_jax_ps_training_matches_single_process():
    """The flagship e2e: 2 JAX worker processes training with the C++ PS
    over localhost TCP reproduce single-process numerics exactly."""
    run_topology(2, 1, WORKER, mode="jax_train",
                 extra={"BYTEPS_PS_MODE": "ps"}, timeout=180)


def test_failure_detection_dead_server():
    """SURVEY.md §5 failure detection: killing a server mid-training must
    fail-stop the fleet via scheduler heartbeat timeout — workers exit
    with a diagnostic instead of hanging, scheduler exits cleanly."""
    import subprocess
    import time

    from tests.ps_utils import free_port, spawn_role, spawn_worker, \
        topology_env

    port = free_port()
    # Recovery off: this test pins the heartbeat-timeout FAIL-STOP for a
    # dead server; the hot-replacement path (recovery on, the default)
    # is covered by test_recovery.py.
    env = topology_env(2, 1, port, {"PS_HEARTBEAT_INTERVAL": "1",
                                    "PS_HEARTBEAT_TIMEOUT": "3",
                                    "BYTEPS_RECOVERY_TIMEOUT_MS": "0"})
    sched = spawn_role("scheduler", env)
    server = spawn_role("server", env)
    workers = [spawn_worker(WORKER, env, r, "slow") for r in range(2)]
    try:
        # wait until both workers are mid-training
        for p in workers:
            for line in p.stdout:
                if line.startswith("step 10"):
                    break
        server.kill()
        t0 = time.time()
        outs = []
        for p in workers:
            out, _ = p.communicate(timeout=30)
            outs.append(out)
            assert p.returncode != 0, "worker should fail-stop, not exit 0"
        detect_s = time.time() - t0
        assert detect_s < 25, f"failure detection too slow: {detect_s}s"
        # Either detection path is correct: the fast path (peer-lost fails
        # the in-flight handle, wait raises) or the heartbeat fail-stop.
        assert any("request(s) in flight" in o
                   or "byteps push/pull failed" in o for o in outs), outs
        sched.communicate(timeout=15)
        assert sched.returncode == 0
    finally:
        for p in (sched, server, *workers):
            if p.poll() is None:
                p.kill()
                p.communicate()


def test_dead_server_fast_fail():
    """VERDICT r2 #9: a push into a dead connection must fail its handle
    in seconds with the server named — the worker-side peer-lost hook +
    send-failure check, not the 30 s heartbeat detector. Over the TCP
    wire, said by name; test_van_shm_dead_server_fast_fail is the ring's
    case."""
    _run_dead_server_fast_fail(TCP)


def test_jax_ps_single_worker_force_distributed():
    """Reference's BYTEPS_FORCE_DISTRIBUTED pattern: one worker still runs
    the full PS path."""
    run_topology(1, 1, WORKER, mode="jax_train",
                 extra={"BYTEPS_PS_MODE": "ps",
                        "BYTEPS_FORCE_DISTRIBUTED": "1"}, timeout=180)


def test_jax_global_api_crosses_fleet():
    """Bare ``bps.push_pull``/``broadcast_parameters`` at host level must
    have Horovod-GLOBAL semantics in PS mode — local chip reduction chained
    with the PS DCN leg — not a silent process-local reduction."""
    run_topology(2, 1, WORKER, mode="jax_global",
                 extra={"BYTEPS_PS_MODE": "ps"}, timeout=180)


def test_jax_ps_bridge_declare_caching():
    """The JAX<->PS bridge registers each tensor once per lifetime (tid
    cache), not once per step (VERDICT r1 missing #2: host-boundary
    overhead)."""
    run_topology(2, 1, WORKER, mode="jax_bridge",
                 extra={"BYTEPS_PS_MODE": "ps"}, timeout=180)


@pytest.mark.parametrize("case,env", [
    ("f32", {}),
    ("bf16_codec", {"BYTEPS_COMPRESSOR": "type=topk;k=4194304"}),
    ("int_leaf", {})])
def test_jax_streamed_push_pull_is_exact(case, env):
    """The per-leaf pipeline of ``ps_push_pull`` over a real fleet: bit for
    bit the numpy sum, in float32, with bfloat16 leaves under a codec, and
    with an integer leaf in the tree."""
    run_topology(2, 1, WORKER, mode="jax_stream",
                 extra={"BYTEPS_PS_MODE": "ps", "BPS_STREAM_CASE": case,
                        **env}, timeout=180)


def _src_dst_rows(extra):
    outs = run_topology(2, 1, WORKER, mode="src_dst", extra=extra,
                        timeout=240)
    rows = [json.loads(ln) for o in outs for ln in o.splitlines()
            if ln.startswith("{")]
    assert len(rows) == 2, outs
    return rows


@pytest.mark.parametrize("extra", [
    {}, TCP, {"BYTEPS_FUSION_BYTES": "0"},
    {"BYTEPS_WIRE_QUANT": "1", "BYTEPS_WIRE_QUANT_MIN_BYTES": "1024"}],
    ids=["rings", "tcp", "unfused", "quantised"])
def test_push_pull_from_a_source_into_a_destination(extra):
    """``Worker.push_pull(tid, src, out=dst)`` over 2 workers: bit for bit
    the in-place call's result (and the numpy sum wherever the wire is
    exact) for float32, int32, float16, an exact and a lossy codec wire and
    the block-quantised one, fused, alone and in 19 partitions; the
    read-only source is what it was."""
    rows = _src_dst_rows({"BYTEPS_PARTITION_BYTES": "65536", **extra})
    assert (rows[0]["fused_frames"] > 0) == (
        "BYTEPS_FUSION_BYTES" not in extra)


def test_a_resent_push_reads_the_source():
    """The same under a van that drops frames (``BYTEPS_CHAOS_DROP``, as
    ``tests/test_chaos.py`` sets it): the retry layer's resend of a dropped
    push reads the source again — not the destination, which holds a
    sentinel — so every sum is still exact, and the counters show that
    frames were dropped and resent."""
    rows = _src_dst_rows({
        "BYTEPS_PARTITION_BYTES": "65536", "BYTEPS_RETRY_TIMEOUT_MS": "200",
        "BYTEPS_CHAOS_SEED": "42", "BYTEPS_CHAOS_DROP": "0.05"})
    assert sum(r["chaos_drop"] for r in rows) > 0, rows
    assert sum(r["retries"] for r in rows) > 0, rows


def test_jax_timeline_combined_capture(tmp_path):
    """One timeline from a real PS-mode training step: jax.profiler device
    events + the C core's DCN push/pull spans merged (VERDICT r1 missing
    #4 / SURVEY.md §5 XPlane interop)."""
    run_topology(1, 1, WORKER, mode="jax_timeline",
                 extra={"BYTEPS_PS_MODE": "ps",
                        "BYTEPS_FORCE_DISTRIBUTED": "1",
                        "BYTEPS_TRACE_ON": "1",
                        "BYTEPS_TRACE_DIR": str(tmp_path / "tr"),
                        "BYTEPS_TRACE_START_STEP": "1",
                        "BYTEPS_TRACE_END_STEP": "3"},
                 timeout=180)


def test_jax_async_seeded_step_updates_not_replaces():
    """Async seeding regression (ISSUE 2 satellite): the step's delta
    pushes must land on the SAME wire keys ps_broadcast seeded, so one
    async SGD step from w=1.0 with grad -4 and lr 0.1 pulls 1.4 — not
    0.4, which is what the first delta silently *becoming* the
    parameters produced when the key derivations diverged."""
    run_topology(1, 1, WORKER, mode="jax_async_seed",
                 extra={"BYTEPS_PS_MODE": "ps", "BYTEPS_ENABLE_ASYNC": "1",
                        "BYTEPS_FORCE_DISTRIBUTED": "1"},
                 timeout=180)


def test_jax_async_training_converges():
    """BYTEPS_ENABLE_ASYNC through the full JAX PS path: stale gradients,
    no per-round barrier, still converges (SURVEY.md §2.7 DP-async)."""
    run_topology(2, 1, WORKER, mode="jax_async",
                 extra={"BYTEPS_PS_MODE": "ps", "BYTEPS_ENABLE_ASYNC": "1"},
                 timeout=180)


def test_jax_bucketed_overlap_matches_single_process():
    """The overlap step, bucketed MULTI-PROGRAM stepping (SURVEY.md §7 hard
    part #1): per-bucket gradient programs + the D2H/DCN/H2D bucket
    pipeline reproduce single-process numerics."""
    run_topology(2, 1, WORKER, mode="jax_bucketed",
                 extra={"BYTEPS_PS_MODE": "ps", "XLA_FLAGS": ""},
                 timeout=240)


def test_jax_bucketed_multichip_bf16_wire():
    """Bucketed overlap under a multi-chip controller with the in-jit
    bf16 wire cast: local pmean inside each bucket program, half the
    boundary bytes, numerics within bf16 tolerance."""
    run_topology(2, 1, WORKER, mode="jax_bucketed",
                 extra={"BYTEPS_PS_MODE": "ps",
                        "XLA_FLAGS":
                            "--xla_force_host_platform_device_count=4",
                        "BPS_OVERLAP_WIRE": "bfloat16",
                        "BPS_BUCKET_N": "3"},
                 timeout=240)


def test_jax_bucketed_with_compression():
    """Bucketed overlap composed with the C-core codec layer (topk+EF on
    the bucketed pushes) — the codec rides the per-leaf declares of the
    tree's binding."""
    run_topology(2, 1, WORKER, mode="jax_bucketed",
                 extra={"BYTEPS_PS_MODE": "ps", "XLA_FLAGS": "",
                        "BPS_OVERLAP_COMPRESSION":
                            "type=topk;k=24;ef=vanilla"},
                 timeout=240)


def test_jax_bucketed_stress_4workers_2servers_compressed_multichip():
    """Composition stress: 4 worker processes x 2 virtual chips each,
    2 servers, the bucketed overlap step (every bucket's program reduces
    over the local mesh), C-core codec with error feedback, and the
    pull-leg re-encode — all at once."""
    run_topology(4, 2, WORKER, mode="jax_bucketed",
                 extra={"BYTEPS_PS_MODE": "ps",
                        "XLA_FLAGS":
                            "--xla_force_host_platform_device_count=2",
                        "BPS_OVERLAP_COMPRESSION":
                            "type=topk;k=24;ef=vanilla"},
                 timeout=300)


def test_mxnet_plugin_over_real_topology():
    """The REAL byteps_tpu.mxnet plugin executes over the REAL PS fleet,
    with only the uninstallable EOL mxnet package replaced by the
    API-faithful stub (tests/mxnet_stub.py): push_pull sum/average,
    broadcast_parameters, DistributedTrainer reduce+rescale."""
    run_topology(2, 1, WORKER, mode="mxnet_stub")


def test_worker_exit_without_shutdown():
    """A worker that never calls shutdown() must still tear down cleanly
    at process exit (C++ Global destructor ordering regression)."""
    import os as _os
    worker = _os.path.join(_os.path.dirname(_os.path.abspath(__file__)),
                           "_no_shutdown_worker.py")
    run_topology(2, 1, worker, timeout=120)


@pytest.mark.ps
def test_topology_clean_under_asan():
    """The basic sum topology plus a no-shutdown worker run clean under
    AddressSanitizer (SURVEY.md §5: the reference has no sanitizer
    coverage; this is how the exit-order use-after-free was caught)."""
    import subprocess

    from byteps_tpu.core.build import build

    gxx = os.environ.get("CXX", "g++")
    libasan = subprocess.run(
        [gxx, "-print-file-name=libasan.so"],
        capture_output=True, text=True).stdout.strip()
    if not libasan or not os.path.isabs(libasan):
        pytest.skip("libasan not available")
    lib = build(sanitize="address", verbose=False)
    extra = {
        "BPS_CORE_LIB": lib,
        "LD_PRELOAD": libasan,
        "ASAN_OPTIONS": "detect_leaks=0:abort_on_error=1",
    }
    # These three legs pin the TCP wire (the one a remote peer gets); the
    # ring's leg follows, and the no-shutdown worker runs on the derived
    # default, so both transports stay under the sanitizer.
    run_topology(2, 1, WORKER, mode="basic", extra={**extra, **TCP},
                 timeout=120)
    # Round-2 concurrency paths: parked pushes + replay (deep
    # pipelining), the cached compressed reply + both-ways codec path,
    # and the byte-credit admission window.
    run_topology(2, 1, WORKER, mode="deep_pipeline",
                 extra={**extra, **TCP}, timeout=120)
    run_topology(2, 1, WORKER, mode="pull_compress",
                 extra={**extra, **TCP}, timeout=180)
    # shm ring transport: MB-scale sustained traffic checks every ring
    # offset/wrap memcpy under ASan redzones.
    run_topology(2, 1, WORKER, mode="congested",
                 extra={**extra, "BYTEPS_VAN_TYPE": "shm"}, timeout=240)
    nsd = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "_no_shutdown_worker.py")
    run_topology(2, 1, nsd, extra=extra, timeout=120)


@pytest.mark.ps
def test_topology_clean_under_tsan():
    """Data-race check on the van/engine/queue threading, including the
    round-2 parked-push replay path (ThreadSanitizer build; OpenMP is
    disabled in it — TSan and OpenMP runtimes don't compose)."""
    import subprocess

    from byteps_tpu.core.build import build

    gxx = os.environ.get("CXX", "g++")
    libtsan = subprocess.run(
        [gxx, "-print-file-name=libtsan.so"],
        capture_output=True, text=True).stdout.strip()
    if not libtsan or not os.path.isabs(libtsan):
        pytest.skip("libtsan not available")
    lib = build(sanitize="thread", verbose=False)
    extra = {
        "BPS_CORE_LIB": lib,
        "LD_PRELOAD": libtsan,
        "TSAN_OPTIONS": "halt_on_error=1:report_bugs=1",
    }
    # The TCP wire by name; the ring's leg follows.
    run_topology(2, 1, WORKER, mode="basic", extra={**extra, **TCP},
                 timeout=240)
    run_topology(2, 1, WORKER, mode="deep_pipeline",
                 extra={**extra, **TCP}, timeout=240)
    # shm transport: the in-process interplay (send threads vs the shm
    # recv thread vs CloseConn/Stop teardown, fd_users refcount, the
    # offer and its answer inside Connect) is TSan-visible; the
    # cross-process ring words themselves are not — their protocol is
    # the seq_cst Dekker pairing in shm_ring.h.
    run_topology(2, 1, WORKER, mode="congested",
                 extra={**extra, "BYTEPS_VAN_TYPE": "shm"}, timeout=240)

"""Worker-side assertions for the localhost PS topology tests.

Runs as a standalone process (one per worker rank); mode selected via
BPS_TEST_MODE. Exits non-zero on any failed assertion — the parent test
reaps exit codes exactly like the reference's run_byteps_test.sh.
"""

import os
import sys

import numpy as np

from byteps_tpu.core import Worker
from byteps_tpu.core.ffi import GROUP_WORKERS


def _trace_dir() -> str:
    """Canonical name first, legacy alias second (ISSUE 5 env unify)."""
    return (os.environ.get("BYTEPS_TRACE_DIR")
            or os.environ["BPS_TRACE_OUT"])


def main() -> int:
    mode = os.environ.get("BPS_TEST_MODE", "basic")
    if mode == "jax_train":
        return jax_train_main()
    if mode == "jax_bridge":
        return jax_bridge_main()
    if mode == "jax_stream":
        return jax_stream_main()
    if mode == "jax_global":
        return jax_global_main()
    if mode == "jax_timeline":
        return jax_timeline_main()
    if mode == "mxnet_stub":
        return mxnet_stub_main()
    if mode == "jax_async":
        return jax_async_main()
    if mode == "jax_async_seed":
        return jax_async_seed_main()
    if mode == "jax_bucketed":
        return jax_bucketed_main()
    if os.environ.get("BPS_TEST_PREINIT_FLIGHT"):
        # Flight-dump rename (ISSUE 7 satellite): a dump taken before
        # the topology exists can only be pid-named; once bps_init
        # learns this rank's identity, SetNode must rename the file to
        # the canonical role/node form (asserted after start below).
        from byteps_tpu.core.ffi import _load
        _load().bps_dump_flight(None)
    w = Worker.start()
    if os.environ.get("BPS_TEST_PREINIT_FLIGHT"):
        td = os.environ.get("BYTEPS_TRACE_DIR") or "./traces"
        pid_file = os.path.join(td, f"flight_r-1_pid{os.getpid()}.json")
        new_file = os.path.join(td, f"flight_r2_n{w.node_id}.json")
        assert not os.path.exists(pid_file), \
            f"pre-topology dump not renamed: {pid_file}"
        assert os.path.exists(new_file), \
            f"renamed flight dump missing: {new_file}"
    rank = w.worker_rank()
    nw = w.num_workers()
    rng = np.random.default_rng(1234)  # same stream on all workers

    try:
        if mode == "basic":
            # sum over workers, several shapes/dtypes, repeated rounds
            for rnd in range(3):
                for shape, dtype in [((64,), "float32"), ((31, 7), "float32"),
                                     ((128,), "float64"), ((16,), "int32"),
                                     ((257,), "float16")]:
                    base = rng.standard_normal(shape)
                    x0 = (base * (rank + 1 + rnd)).astype(dtype)
                    expect = sum(
                        (base * (r + 1 + rnd)).astype(dtype).astype("float64")
                        for r in range(nw))
                    name = f"t_{shape}_{dtype}"
                    tid = w.declare(name, int(np.prod(shape)), dtype,
                                    compression="")
                    arr = np.ascontiguousarray(x0)
                    h = w.push_pull(tid, arr, average=False)
                    w.wait(h)
                    # fp16: each pairwise add rounds to half precision
                    rtol = 2e-3 if dtype == "float16" else 1e-5
                    np.testing.assert_allclose(
                        arr.astype("float64"), expect.reshape(shape),
                        rtol=rtol, atol=1e-8)

        elif mode == "src_dst":
            # A push has a source and a destination: the core reads the
            # contribution from one array — first send, a fused frame's
            # gather, a retry's resend — and writes the aggregate into
            # another. Every tensor goes round twice a round, from a
            # read-only source into a destination that holds a sentinel (a
            # read of the destination in the source's place would push
            # it), and in place under a name of its own, which is the call
            # as it always was: the two results must be equal to the bit,
            # the numpy sum where the wire is exact, and the source what
            # it was. Sizes: one fused with its neighbours, one a frame of
            # its own, one of 19 partitions (BYTEPS_PARTITION_BYTES 65536).
            import json

            quantised = os.environ.get("BYTEPS_WIRE_QUANT", "") not in (
                "", "0")  # the float32 wire is then block-quantised
            sizes = (48, 20_000, 300_000)
            wires = [("f32", "float32", ""), ("int", "int32", ""),
                     ("f16", "float16", ""),
                     ("topk", "float32", f"type=topk;k={sizes[-1]}"),
                     ("onebit", "float32", "type=onebit")]
            tids = {(name, n, twin): w.declare(
                        f"sd_{name}_{n}_{twin}", n, dtype, compression=comp)
                    for name, dtype, comp in wires for n in sizes
                    for twin in "ab"}
            for rnd in range(3):
                average = rnd == 1
                flight = []
                for name, dtype, _ in wires:
                    for n in sizes:
                        base = (np.arange(n) % 61 + rnd
                                - (0 if name == "int" else 30)).astype(dtype)
                        src = np.ascontiguousarray(base * (rank + 1))
                        kept, same = src.copy(), src.copy()
                        src.flags.writeable = False
                        dst = np.full_like(src, 7777)
                        flight.append((
                            w.push_pull(tids[name, n, "a"], src,
                                        average=average, out=dst),
                            w.push_pull(tids[name, n, "b"], same,
                                        average=average),
                            name, base, src, kept, dst, same))
                for h, h_same, name, base, src, kept, dst, same in flight:
                    w.wait(h)
                    w.wait(h_same)
                    assert src.tobytes() == kept.tobytes(), (rnd, name)
                    assert dst.tobytes() == same.tobytes(), (rnd, name)
                    if name == "onebit" or (quantised and name == "f32"):
                        continue  # a lossy wire: the twin is the witness
                    total = base.astype(np.float64) * sum(
                        r + 1 for r in range(nw))
                    if average:
                        total = total // nw if name == "int" else total / nw
                    np.testing.assert_array_equal(
                        dst, total.astype(dst.dtype), err_msg=f"{rnd} {name}")
            w.barrier(GROUP_WORKERS)  # all counters final
            snap = w.metrics_snapshot()["counters"]
            print(json.dumps({
                "retries": snap.get("bps_retries_total", 0),
                "chaos_drop": snap.get("bps_chaos_drop_total", 0),
                "fused_frames": snap.get("bps_fused_msgs_total", 0)}),
                flush=True)
            w.barrier(GROUP_WORKERS)

        elif mode == "average":
            tid = w.declare("avg", 50, "float32", compression="")
            arr = np.full(50, float(rank + 1), dtype=np.float32)
            h = w.push_pull(tid, arr, average=True)
            w.wait(h)
            expect = sum(r + 1 for r in range(nw)) / nw
            np.testing.assert_allclose(arr, expect, rtol=1e-6)

        elif mode == "multipart":
            # tensor >> partition_bytes so it spans partitions and servers
            n = 300_000  # 1.2 MB f32; BYTEPS_PARTITION_BYTES set to 65536
            tid = w.declare("big", n, "float32", compression="")
            base = rng.standard_normal(n).astype(np.float32)
            arr = np.ascontiguousarray(base * (rank + 1))
            import time as _t
            t0 = _t.monotonic()
            h = w.push_pull(tid, arr, average=False)
            w.wait(h)
            # For a paced fleet (test_pacing_rate_path): the pace held if
            # this took at least the bytes over the rate.
            print(f"push_pull_s {_t.monotonic() - t0:.3f}", flush=True)
            scale = sum(r + 1 for r in range(nw))
            np.testing.assert_allclose(arr, base * scale, rtol=1e-4,
                                       atol=1e-5)

        elif mode == "broadcast":
            tid = w.declare("bc", 1000, "float32", compression="")
            if rank == 0:
                arr = rng.standard_normal(1000).astype(np.float32)
            else:
                arr = np.zeros(1000, dtype=np.float32)
            root_val = rng2 = None
            h = w.broadcast(tid, arr, root_rank=0)
            w.wait(h)
            # all ranks must hold rank0's values: regenerate rank0's stream
            check = np.random.default_rng(1234).standard_normal(1000).astype(
                np.float32)
            np.testing.assert_allclose(arr, check, rtol=1e-6)

        elif mode == "rebroadcast":
            # Re-broadcasting the same tensor (epoch-boundary weight
            # re-sync) must deliver the NEW root values every round, never
            # a stale previous round (server bcast_version ordering).
            tid = w.declare("rb", 256, "float32", compression="")
            for rnd in range(4):
                if rank == 0:
                    arr = np.full(256, float(100 + rnd), dtype=np.float32)
                else:
                    arr = np.zeros(256, dtype=np.float32)
                h = w.broadcast(tid, arr, root_rank=0)
                w.wait(h)
                np.testing.assert_allclose(arr, 100.0 + rnd)
                w.barrier(GROUP_WORKERS)

        elif mode == "byte_credit":
            # Byte-budget admission: huge tensor (16 partitions of 64 KiB)
            # under a 128 KiB budget -> at most 2 partitions in flight at
            # any instant; a small tensor declared later still completes.
            import json
            n_huge = 16 * 16384  # 16 partitions at BYTEPS_PARTITION_BYTES
            tid_h = w.declare("huge", n_huge, "float32", compression="")
            tid_s = w.declare("small", 256, "float32", compression="")
            big = np.ones(n_huge, dtype=np.float32)
            small = np.ones(256, dtype=np.float32)
            h1 = w.push_pull(tid_h, big, average=False)
            h2 = w.push_pull(tid_s, small, average=False)
            w.wait(h1)
            w.wait(h2)
            np.testing.assert_allclose(big, float(nw))
            np.testing.assert_allclose(small, float(nw))
            path = os.path.join(_trace_dir(),
                                f"credit_rank{rank}.json")
            assert w.dump_trace(path) > 0
            with open(path) as f:
                evs = json.load(f)["traceEvents"]
            pushes = {e["args"]["key"]: e for e in evs if e["name"] == "push"}
            pulls = {e["args"]["key"]: e for e in evs if e["name"] == "pull"}
            huge_keys = [k for k in pushes if (k >> 16) == tid_h]
            assert len(huge_keys) == 16, huge_keys
            # The measured push-issue..pull-complete span is a sub-window
            # of the credit window, so measured concurrency can only
            # under-count — peak > 2 proves the byte cap was violated.
            marks = []
            for k in huge_keys:
                marks.append((pushes[k]["ts"], 1))
                marks.append((pulls[k]["ts"] + pulls[k]["dur"], -1))
            cur = peak = 0
            for _, d in sorted(marks):
                cur += d
                peak = max(peak, cur)
            assert peak <= 2, f"byte credit violated: {peak} in flight"

        elif mode == "credit_budget":
            # ISSUE 48: the byte budget in force — ten partitions' worth
            # by default, a forced BYTEPS_SCHEDULING_CREDIT to the byte (a
            # legacy count times the partition size) — is what stands in
            # flight when nothing can land: rank 0 pushes late, so the
            # server can answer no pull and every other worker's queue
            # holds exactly what its budget admits, no byte more.
            import time
            part = int(os.environ["BYTEPS_PARTITION_BYTES"])
            forced = int(os.environ.get("BYTEPS_SCHEDULING_CREDIT", "0"))
            budget = (forced * part if 0 < forced < 1024
                      else forced or 10 * part)
            n_parts = 32
            tid = w.declare("wide", n_parts * (part // 4), "float32",
                            compression="")
            wide = np.ones(n_parts * (part // 4), dtype=np.float32)
            w.wait(w.push_pull(tid, wide, average=False))  # ranks in step
            np.testing.assert_allclose(wide, float(nw))
            wide[:] = 2.0
            if rank == 0:
                time.sleep(1.5)
            h = w.push_pull(tid, wide, average=False)
            if rank != 0:
                time.sleep(0.5)
                q = w.metrics_snapshot()["queue"]
                # whole partitions, at least one (always-admit-one)
                admitted = min(n_parts, max(1, budget // part))
                assert q["credit_budget_bytes"] == budget, q
                assert q["inflight_bytes"] == admitted * part, q
                assert q["pending"] == n_parts - admitted, q
            w.wait(h)
            np.testing.assert_allclose(wide, float(2 * nw))
            q = w.metrics_snapshot()["queue"]
            assert (q["inflight_bytes"], q["pending"]) == (0, 0), q
            print(f"credit_budget {q['credit_budget_bytes']}")

        elif mode == "priority":
            # The reference's scheduling rationale: an EARLIER-declared
            # (front-of-model) tensor preempts a later-declared one at
            # the queue even when enqueued second. Per round: a "plug"
            # soaks up the 1-partition byte budget, then LATE is enqueued
            # before EARLY. In a round where both enqueues beat the
            # plug's round trip, a priority scheduler pops ALL of early
            # first — min(early push ts) < min(late push ts) — a
            # signature FIFO (or inverted priority) can NEVER produce,
            # since late entered the queue first. On a loaded 1-core box
            # a round can degenerate (late drains before early is even
            # enqueued), so assert the signature appears in >= 1 of 12
            # rounds (empirically most rounds are non-degenerate).
            import json
            n = 4 * 16384  # 4 partitions at BYTEPS_PARTITION_BYTES=65536
            rounds = 12
            plug = np.ones(16384, dtype=np.float32)
            a = np.ones(n, dtype=np.float32)
            b = np.ones(n, dtype=np.float32)
            tids = []
            for rnd in range(rounds):
                tid_plug = w.declare(f"plug{rnd}", 16384, "float32",
                                     compression="")
                tid_early = w.declare(f"early{rnd}", n, "float32",
                                      compression="")
                tid_late = w.declare(f"late{rnd}", n, "float32",
                                     compression="")
                tids.append((tid_early, tid_late))
                h_plug = w.push_pull(tid_plug, plug, average=False)
                h_late = w.push_pull(tid_late, b, average=False)
                h_early = w.push_pull(tid_early, a, average=False)
                w.wait(h_plug)
                w.wait(h_late)
                w.wait(h_early)
            path = os.path.join(_trace_dir(),
                                f"prio_rank{rank}.json")
            assert w.dump_trace(path) > 0
            with open(path) as f:
                evs = json.load(f)["traceEvents"]
            pushes = [e for e in evs if e["name"] == "push"]
            signal = 0
            for tid_early, tid_late in tids:
                early_ts = [e["ts"] for e in pushes
                            if (e["args"]["key"] >> 16) == tid_early]
                late_ts = [e["ts"] for e in pushes
                           if (e["args"]["key"] >> 16) == tid_late]
                assert len(early_ts) == 4 and len(late_ts) == 4
                if min(early_ts) < min(late_ts):
                    signal += 1
            if os.environ.get("BYTEPS_SCHEDULING") == "fifo":
                # A/B inverse: under FIFO the earlier-declared tensor can
                # NEVER jump ahead of the later one enqueued before it —
                # the signature must vanish entirely.
                assert signal == 0, (
                    f"FIFO mode showed priority preemption in {signal} "
                    "rounds — BYTEPS_SCHEDULING=fifo is not honored")
            else:
                assert signal >= 1, (
                    f"no priority preemption observed in {rounds} rounds: "
                    "the earlier-declared tensor never popped ahead of the "
                    "later-declared one enqueued before it")

        elif mode == "deep_pipeline":
            # 4 rounds of ONE tensor in flight before any wait: rounds
            # r+2/r+3 map onto slots still serving r/r+1, so the server
            # must park those pushes (backpressure), not fail-stop. Each
            # round's aggregate must still be exact.
            n = 2048
            tid = w.declare("deep", n, "float32", compression="")
            base = rng.standard_normal(n).astype(np.float32)
            arrs = [np.ascontiguousarray(base * (rank + 1) * (i + 1))
                    for i in range(4)]
            handles = [w.push_pull(tid, a, average=False) for a in arrs]
            for h in handles:
                w.wait(h)
            scale = sum(r + 1 for r in range(nw))
            for i, a in enumerate(arrs):
                np.testing.assert_allclose(
                    a, base * scale * (i + 1), rtol=1e-4, atol=1e-5)

        elif mode == "slow_job":
            # The worker idles past the old 30 s finalize grace before its
            # first push: the fleet (scheduler + servers) must still be
            # serving. Regression for the bounded Finalize wait that
            # silently killed any fleet whose job outlived 30 s.
            import time as _t
            _t.sleep(35)
            n = 4096
            tid = w.declare("late", n, "float32", compression="")
            arr = np.full(n, float(rank + 1), np.float32)
            h = w.push_pull(tid, arr, average=False)
            w.wait(h)
            expect = sum(r + 1 for r in range(nw))
            np.testing.assert_allclose(arr, expect)

        elif mode == "congested":
            # Many MB-sized tensors with several rounds in flight over
            # deliberately tiny kernel socket buffers: with response
            # callbacks on the van recv threads this deadlocks (the recv
            # thread blocks sending the chained PULL into a full socket
            # and stops reading — both directions wedge); the key-hashed
            # callback executor must keep the readers draining.
            n = 1 << 18  # 1 MB per tensor
            tids = [w.declare(f"cg{i}", n, "float32", compression="")
                    for i in range(8)]
            rounds = []
            base = rng.standard_normal(n).astype(np.float32)
            for r in range(3):
                arrs = [np.ascontiguousarray(base * (rank + 1 + i + r))
                        for i in range(len(tids))]
                rounds.append(
                    [(w.push_pull(t, a, average=False), a)
                     for t, a in zip(tids, arrs)])
            for r, batch in enumerate(rounds):
                for i, (h, a) in enumerate(batch):
                    w.wait(h)
                    expect = sum(rr + 1 + i + r for rr in range(nw))
                    np.testing.assert_allclose(a, base * expect,
                                               rtol=1e-4, atol=1e-4)

        elif mode == "handles":
            # several in-flight handles; poll semantics
            tids = [w.declare(f"h{i}", 4096, "float32", compression="")
                    for i in range(8)]
            arrs = [np.full(4096, float(i + rank), np.float32)
                    for i in range(8)]
            handles = [w.push_pull(t, a, average=False)
                       for t, a in zip(tids, arrs)]
            for h in handles:
                w.wait(h)
                assert w.poll(h)
            for i, a in enumerate(arrs):
                expect = sum(i + r for r in range(nw))
                np.testing.assert_allclose(a, expect)

        elif mode == "onebit":
            # semantics vs a numpy reference of the codec (single worker):
            # decompress(compress(x)) == sign(x) * mean(|x|)
            x = rng.standard_normal(1000).astype(np.float32)
            tid = w.declare("ob", 1000, "float32", compression="type=onebit")
            arr = x.copy()
            h = w.push_pull(tid, arr, average=False)
            w.wait(h)
            expect = np.where(x >= 0, 1.0, -1.0) * np.abs(x).mean()
            np.testing.assert_allclose(arr, expect, rtol=1e-5, atol=1e-6)

        elif mode == "topk_lossless":
            # k = n makes topk exact; aggregation must then match plain sum
            n = 256
            base = rng.standard_normal(n).astype(np.float32)
            x = base * (rank + 1)
            tid = w.declare("tk", n, "float32", compression=f"type=topk;k={n}")
            arr = x.copy()
            h = w.push_pull(tid, arr, average=False)
            w.wait(h)
            scale = sum(r + 1 for r in range(nw))
            np.testing.assert_allclose(arr, base * scale, rtol=1e-5,
                                       atol=1e-5)

        elif mode == "pull_compress":
            # Pull-leg compression: with a codec declared, the server
            # re-encodes pull responses, so DCN bytes drop in BOTH
            # directions vs an identical uncompressed tensor.
            n = 100_000
            base = rng.standard_normal(n).astype(np.float32)
            tid_raw = w.declare("pc_raw", n, "float32", compression="")
            tid_ob = w.declare("pc_ob", n, "float32",
                               compression="type=onebit")
            w.barrier(GROUP_WORKERS)
            s0, r0 = w.net_bytes()
            arr = base.copy()
            h = w.push_pull(tid_raw, arr, average=False)
            w.wait(h)
            w.barrier(GROUP_WORKERS)
            s1, r1 = w.net_bytes()
            arr2 = base.copy()
            h = w.push_pull(tid_ob, arr2, average=False)
            w.wait(h)
            w.barrier(GROUP_WORKERS)
            s2, r2 = w.net_bytes()
            raw_sent, raw_recv = s1 - s0, r1 - r0
            ob_sent, ob_recv = s2 - s1, r2 - r1
            assert raw_sent > n * 4 and raw_recv > n * 4, (raw_sent, raw_recv)
            assert ob_sent < raw_sent / 8, (ob_sent, raw_sent)
            assert ob_recv < raw_recv / 8, (ob_recv, raw_recv)
            # onebit is idempotent on its own output, so the doubly-
            # compressed aggregate is still exact for identical pushes.
            dec = (np.where(base >= 0, 1.0, -1.0).astype(np.float32)
                   * np.abs(base).mean())
            np.testing.assert_allclose(arr2, dec * nw, rtol=1e-4, atol=1e-5)

        elif mode == "error_feedback":
            # with ef, repeated rounds of a CONSTANT gradient must converge
            # in mean: residual accumulation corrects the onebit bias.
            n = 512
            g = rng.standard_normal(n).astype(np.float32)
            tid = w.declare("ef", n, "float32",
                            compression="type=onebit;ef=vanilla")
            total = np.zeros(n, dtype=np.float64)
            rounds = 200
            for _ in range(rounds):
                arr = g.copy()
                h = w.push_pull(tid, arr, average=True)
                w.wait(h)
                total += arr
            mean_recv = total / rounds
            err = np.abs(mean_recv - g).mean() / (np.abs(g).mean() + 1e-9)
            assert err < 0.05, f"error feedback failed to converge: {err}"

        elif mode == "async":
            # async mode: server-resident accumulator, immediate replies
            tid = w.declare("as", 16, "float32", compression="")
            for step in range(1, 4):
                arr = np.full(16, 1.0, dtype=np.float32)
                h = w.push_pull(tid, arr, average=False, async_mode=True)
                w.wait(h)
            # after 3 pushes of ones (any interleaving), the pulled value is
            # between my 3 pushes and nw*3 total pushes
            assert arr[0] >= 3.0 - 1e-6 and arr[0] <= 3.0 * nw + 1e-6, arr[0]
            # staleness telemetry (round 5): every async pull records how
            # many fleet pushes landed between our push and our pull
            st = w.async_staleness()
            assert st["samples"] == 3, st
            assert 0 <= st["mean"] <= st["max"] <= 3 * (nw - 1), st

        elif mode == "trace":
            tid = w.declare("tr", 1 << 16, "float32", compression="")
            arr = np.ones(1 << 16, dtype=np.float32)
            h = w.push_pull(tid, arr, average=False)
            w.wait(h)
            path = os.path.join(_trace_dir(),
                                f"trace_rank{rank}.json")
            n = w.dump_trace(path)
            assert n > 0, "no trace events recorded"
            import json
            with open(path) as f:
                data = json.load(f)
            stages = {e["name"] for e in data["traceEvents"]}
            assert "push" in stages and "pull" in stages, stages

        elif mode == "slow":
            # long-running rounds; used by the failure-detection test
            import time
            tid = w.declare("slow", 1024, "float32", compression="")
            for i in range(500):
                arr = np.ones(1024, dtype=np.float32)
                h = w.push_pull(tid, arr, average=False)
                w.wait(h)
                time.sleep(0.2)
                if i % 10 == 0:
                    print(f"step {i}", flush=True)

        elif mode == "fast_fail":
            # One good round, then the harness kills the server; the next
            # push's wait must raise promptly with the node named —
            # NOT hang until the heartbeat detector (VERDICT r2 weak #7).
            import time
            tid = w.declare("ff", 4096, "float32", compression="")
            arr = np.ones(4096, np.float32)
            w.wait(w.push_pull(tid, arr, average=False))
            print("ready", flush=True)
            time.sleep(3)  # server is killed inside this window
            t0 = time.time()
            try:
                h = w.push_pull(tid, np.ones(4096, np.float32),
                                average=False)
                w.wait(h)
                print("ERROR: wait returned without failure", flush=True)
                return 1
            except RuntimeError as e:
                dt = time.time() - t0
                assert dt < 5.0, f"fast-fail too slow: {dt:.1f}s"
                assert "node" in str(e), e
                print(f"fast-fail OK in {dt:.2f}s: {e}", flush=True)

        elif mode == "monitor":
            # Live-telemetry acceptance (docs/monitoring.md): after a
            # fleet-wide push_pull, every role's /metrics endpoint must
            # serve Prometheus-parseable text whose worker-side
            # bps_push_bytes_total sum equals the server-side
            # bps_recv_bytes_total sum exactly (both sides count CMD_PUSH
            # payload bytes).
            import json
            import urllib.request

            from byteps_tpu.monitor.metrics import parse_prometheus

            base = int(os.environ["BYTEPS_MONITOR_PORT"])
            ns = int(os.environ["DMLC_NUM_SERVER"])
            n = 50_000
            tid = w.declare("mon", n, "float32", compression="")
            arr = np.full(n, float(rank + 1), np.float32)
            h = w.push_pull(tid, arr, average=False)
            w.wait(h)
            np.testing.assert_allclose(arr, sum(r + 1 for r in range(nw)))
            # All workers' pulls completed -> every server's push/reply
            # counters are final before anyone scrapes.
            w.barrier(GROUP_WORKERS)

            def scrape(port):
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/metrics",
                        timeout=5) as r:
                    return parse_prometheus(r.read().decode())

            my_port = base + 1 + ns + rank
            own = scrape(my_port)
            assert own["bps_push_bytes_total"][()] == n * 4, own[
                "bps_push_bytes_total"]
            assert own["bps_up"][(("role", "worker"),
                                  ("node_id", str(1 + ns + rank)))] == 1
            # The push latency histogram saw exactly this worker's
            # partitions, and its +Inf bucket equals its count.
            n_parts = own["bps_push_partitions_total"][()]
            assert own["bps_push_us_count"][()] == n_parts > 0
            inf_key = (("le", "+Inf"),)
            assert own["bps_push_us_bucket"][inf_key] == n_parts
            if rank == 0:
                worker_push = sum(
                    scrape(base + 1 + ns + r)["bps_push_bytes_total"][()]
                    for r in range(nw))
                server_recv = sum(
                    scrape(base + 1 + s)["bps_recv_bytes_total"][()]
                    for s in range(ns))
                assert worker_push == server_recv == nw * n * 4, (
                    worker_push, server_recv)
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{my_port}/healthz",
                        timeout=5) as r:
                    health = json.loads(r.read().decode())
                assert r.status == 200 and health["status"] == "ok", health
            # Hold the fleet (and its endpoints) until rank 0 finished
            # scraping everyone.
            w.barrier(GROUP_WORKERS)

        elif mode == "monitor_hold":
            # Straggler-detection harness: MB-scale rounds (the parent
            # pacing-limits one worker's sends so its push latency
            # genuinely inflates), then hold the fleet alive until the
            # parent's monitor.top scrape is done (go-file handshake).
            import time
            n = 1 << 18  # 1 MB float32, one partition
            tid = w.declare("hold", n, "float32", compression="")
            for _ in range(3):
                arr = np.ones(n, np.float32)
                h = w.push_pull(tid, arr, average=False)
                w.wait(h)
                np.testing.assert_allclose(arr, float(nw))
            print("ready", flush=True)
            go = os.environ.get("BPS_TEST_GO_FILE", "")
            deadline = time.time() + 60
            while go and not os.path.exists(go) and time.time() < deadline:
                time.sleep(0.2)

        elif mode == "insight_hold":
            # Per-round introspection harness (ISSUE 7): R comm-only
            # rounds over parameterized keys, then print this worker's
            # round-gauge snapshot + local round summary and hold the
            # fleet (go-file) while the parent scrapes the scheduler's
            # /rounds fleet table. Key shape/count and round count come
            # from env so one mode serves both the wire-starved
            # (fusion off, sub-64KB keys) and the pacing-straggler
            # variants.
            import json
            import time

            nelem = int(os.environ.get("BPS_TEST_INSIGHT_N", "2048"))
            nkeys = int(os.environ.get("BPS_TEST_INSIGHT_KEYS", "24"))
            rounds = int(os.environ.get("BPS_TEST_INSIGHT_ROUNDS", "6"))
            tids = [w.declare(f"in{i}", nelem, "float32", compression="")
                    for i in range(nkeys)]
            for rnd in range(rounds):
                staged = []
                for i, tid in enumerate(tids):
                    base = (np.arange(nelem) % 31 + i + rnd + 1).astype(
                        np.float32)
                    arr = np.ascontiguousarray(base * (rank + 1))
                    staged.append((w.push_pull(tid, arr, average=False),
                                   arr, base))
                scale = sum(r + 1 for r in range(nw))
                for h, arr, base in staged:
                    w.wait(h)
                    np.testing.assert_array_equal(arr, base * scale)
            # Sentinel round: a round only finalizes into the ring when
            # a LATER round starts (mid-step completions must not split
            # records), so one extra single-key push closes round R-1.
            sent = np.ones(nelem, np.float32)
            w.wait(w.push_pull(tids[0], sent, average=False))
            # Let at least one heartbeat ship the freshly closed rounds
            # to the scheduler before the parent scrapes (interval 1s).
            time.sleep(2.5)
            w.barrier(GROUP_WORKERS)  # all rounds' gauges final
            snap = w.metrics_snapshot()
            from byteps_tpu.core.ffi import round_summary
            local = round_summary()
            print(json.dumps({
                "node_id": snap["node"]["id"],
                "rounds_completed": snap["counters"].get(
                    "bps_rounds_completed_total", 0),
                "gauges": {k: v for k, v in snap["gauges"].items()
                           if k.startswith("bps_round_")},
                "local_last": local["last"],
                "local_rounds": [r["round"] for r in local["rounds"]],
            }), flush=True)
            print("ready", flush=True)
            go = os.environ.get("BPS_TEST_GO_FILE", "")
            deadline = time.time() + 60
            while go and not os.path.exists(go) and time.time() < deadline:
                time.sleep(0.2)
            w.barrier(GROUP_WORKERS)

        elif mode == "fusion":
            # Small-tensor fusion acceptance: a conv-net-shaped flood of
            # tiny tensors must aggregate EXACTLY (integer-valued floats,
            # so float summation is exact and the digest is bitwise
            # comparable across fusion-on and fusion-off runs), and the
            # worker/server push-byte parity contract must hold under
            # fusion. Emits this worker's digest and wire counters; the
            # parent test diffs them between runs.
            import hashlib
            import json
            import urllib.request

            from byteps_tpu.monitor.metrics import parse_prometheus

            sizes = [64, 96, 128, 192, 256, 384, 512, 768, 1024, 1536,
                     2048, 3072] * 8  # 96 tensors, 256 B .. 12 KiB
            tids = [w.declare(f"fu{i}", n, "float32", compression="")
                    for i, n in enumerate(sizes)]
            digest = hashlib.sha256()
            rounds = 3
            for rnd in range(rounds):
                staged = []
                for i, (tid, n) in enumerate(zip(tids, sizes)):
                    base = (np.arange(n) % 97 + i + rnd).astype(np.float32)
                    arr = np.ascontiguousarray(base * (rank + 1))
                    staged.append((tid, arr, base))
                # Enqueue everything before waiting: the backlog is what
                # the fusion collector coalesces.
                handles = [(w.push_pull(t, a, average=False), a, b)
                           for t, a, b in staged]
                for h, a, base in handles:
                    w.wait(h)
                    expect = base * sum(r + 1 for r in range(nw))
                    np.testing.assert_array_equal(a, expect)
                    digest.update(a.tobytes())
            w.barrier(GROUP_WORKERS)  # all counters final before scraping
            snap = w.metrics_snapshot()["counters"]
            parity = None
            mport = int(os.environ.get("BYTEPS_MONITOR_PORT", "0"))
            if rank == 0 and mport:
                ns = int(os.environ["DMLC_NUM_SERVER"])

                def scrape(port):
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{port}/metrics",
                            timeout=5) as r:
                        return parse_prometheus(r.read().decode())

                worker_push = sum(
                    scrape(mport + 1 + ns + r)["bps_push_bytes_total"][()]
                    for r in range(nw))
                server_recv = sum(
                    scrape(mport + 1 + s)["bps_recv_bytes_total"][()]
                    for s in range(ns))
                assert worker_push == server_recv, (worker_push,
                                                    server_recv)
                parity = [worker_push, server_recv]
            print(json.dumps({
                "digest": digest.hexdigest(),
                "fused": snap.get("bps_fused_msgs_total", 0),
                "frames": snap.get("bps_van_sent_frames_total", 0),
                "push_partitions": snap.get("bps_push_partitions_total",
                                            0),
                "push_bytes": snap.get("bps_push_bytes_total", 0),
                "parity": parity,
            }), flush=True)
            # Hold the fleet until rank 0 finished scraping everyone.
            w.barrier(GROUP_WORKERS)

        elif mode == "fusion_pipeline":
            # Ack-on-park regression: many small tensors DEEP-PIPELINED —
            # every round's push_pull for every key issued before any
            # wait — with fusion on. Rounds r+2/r+3 map onto slots still
            # serving r/r+1, so fused frames carry sub-pushes the server
            # must park, and frames MIX rounds (the collector's
            # duplicate-key flush splits one key's back-to-back rounds
            # across frames). If a parked sub-push withheld its frame's
            # batched CMD_MULTI_ACK until its slot recycled, two workers'
            # frames could each gate the pull the other's parked push
            # needs (ack -> slot-recycle -> pull -> ack), which this test
            # would hit as a timeout; the server must instead record a
            # parked sub-push's ack at park time. Every round's aggregate
            # must still be exact (integer-valued floats).
            sizes = [64, 96, 128, 192, 256, 384, 512] * 6  # 42 tensors
            tids = [w.declare(f"fp{i}", n, "float32", compression="")
                    for i, n in enumerate(sizes)]
            scale = sum(r + 1 for r in range(nw))
            handles = []
            for rnd in range(4):
                for i, (tid, n) in enumerate(zip(tids, sizes)):
                    base = (np.arange(n) % 23 + i + 1).astype(np.float32)
                    arr = np.ascontiguousarray(
                        base * (rank + 1) * (rnd + 1))
                    expect = base * scale * (rnd + 1)
                    handles.append(
                        (w.push_pull(tid, arr, average=False), arr,
                         expect))
            for h, arr, expect in handles:
                w.wait(h)
                np.testing.assert_array_equal(arr, expect)

        elif mode == "quant":
            # Block-quantized wire acceptance (ISSUE 6): a mixed-size
            # multi-round workload with BYTEPS_WIRE_QUANT set by the
            # parent. Keys at or above BYTEPS_WIRE_QUANT_MIN_BYTES ship
            # int8-encoded (verified within EF tolerance of the exact
            # dense aggregate); keys below it — and one lossless-codec
            # key, proving codec keys skip quant — stay EXACT. The
            # digest over every final buffer is the cross-run oracle:
            # the quantized wire is deterministic, so chaos / recovery
            # variants must reproduce the fault-free quant run bitwise.
            import hashlib
            import json
            import urllib.request

            from byteps_tpu.monitor.metrics import parse_prometheus

            quant_on = os.environ.get(
                "BYTEPS_WIRE_QUANT", "") not in ("", "0")
            min_bytes = int(os.environ.get(
                "BYTEPS_WIRE_QUANT_MIN_BYTES", "1024"))
            # 256 B .. 12 KiB raw: both sides of the default 1 KiB
            # min-bytes gate, fused and singleton flushes.
            sizes = [64, 96, 128, 192, 256, 384, 512, 768, 1024, 1536,
                     2048, 3072] * 4  # 48 tensors
            tids = [w.declare(f"qt{i}", n, "float32", compression="")
                    for i, n in enumerate(sizes)]
            # Lossless per-tensor codec key: topk with k=n roundtrips
            # exactly AND must bypass the quantized wire (codec keys
            # ship compressor bytes).
            ck = w.declare("qt_comp", 512, "float32",
                           compression="type=topk;k=512")
            digest = hashlib.sha256()
            scale = sum(r + 1 for r in range(nw))
            for rnd in range(3):
                staged = []
                for i, (tid, n) in enumerate(zip(tids, sizes)):
                    base = (np.arange(n) % 97 + i + rnd + 1).astype(
                        np.float32)
                    arr = np.ascontiguousarray(base * (rank + 1))
                    staged.append((w.push_pull(tid, arr, average=False),
                                   arr, base, n))
                cbase = (np.arange(512) % 41 + rnd + 1).astype(np.float32)
                carr = np.ascontiguousarray(cbase * (rank + 1))
                ch = w.push_pull(ck, carr, average=False)
                for h, arr, base, n in staged:
                    w.wait(h)
                    expect = base * scale
                    if quant_on and n * 4 >= min_bytes:
                        # EF tolerance: per push, the int8 rounding
                        # error is at most absmax/254 per element (per
                        # block), the EF residual carries at most one
                        # more step, and the re-quantized reply adds
                        # one step of the aggregate — comfortably
                        # inside 3% of the aggregate's magnitude, and
                        # orders of magnitude tighter than any
                        # double-apply or mis-decode bug.
                        tol = float(np.abs(expect).max()) * 0.03 + 1e-3
                        np.testing.assert_allclose(arr, expect, rtol=0,
                                                   atol=tol)
                    else:
                        np.testing.assert_array_equal(arr, expect)
                    digest.update(arr.tobytes())
                w.wait(ch)
                np.testing.assert_array_equal(carr, cbase * scale)
                digest.update(carr.tobytes())
            w.barrier(GROUP_WORKERS)  # all counters final
            snap = w.metrics_snapshot()["counters"]
            parity = None
            sched_fleet_workers = None
            mport = int(os.environ.get("BYTEPS_MONITOR_PORT", "0"))
            if rank == 0 and mport:
                # Round summaries flowing under quant+chaos (ISSUE 7
                # acceptance): poll the scheduler's /rounds until its
                # fleet table holds every worker's heartbeat summaries
                # (heartbeats are control-plane: chaos never touches
                # them, so summaries must arrive even mid-fault).
                import time as _time
                deadline = _time.time() + 10
                while _time.time() < deadline:
                    try:
                        with urllib.request.urlopen(
                                f"http://127.0.0.1:{mport}/rounds",
                                timeout=5) as r:
                            fleet = json.loads(r.read().decode())[
                                "fleet"]
                        sched_fleet_workers = sum(
                            1 for st in fleet.values()
                            if st.get("role") == 2
                            and st.get("updates", 0) > 0)
                        if sched_fleet_workers >= nw:
                            break
                    except OSError:
                        pass
                    _time.sleep(0.5)
                # Push-byte parity under quant: both sides must count
                # ENCODED wire bytes (the PR 2 contract, re-proven on
                # the quantized wire). NOT asserted under chaos: the
                # server counts every ARRIVAL (retry resends and chaos
                # dups included) while the worker counts each partition
                # once, so injected faults legitimately skew the sums —
                # and a failed assert here would skip the final barrier
                # and wedge the peer worker in it forever.
                chaos_armed = any(
                    float(os.environ.get(v, "0") or 0) > 0
                    for v in ("BYTEPS_CHAOS_DROP", "BYTEPS_CHAOS_DUP",
                              "BYTEPS_CHAOS_RESET_EVERY",
                              "BYTEPS_CHAOS_CORRUPT"))
                ns = int(os.environ["DMLC_NUM_SERVER"])

                def scrape(port):
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{port}/metrics",
                            timeout=5) as r:
                        return parse_prometheus(r.read().decode())

                if not chaos_armed:
                    worker_push = sum(
                        scrape(mport + 1 + ns + r)
                        ["bps_push_bytes_total"][()] for r in range(nw))
                    server_recv = sum(
                        scrape(mport + 1 + s)["bps_recv_bytes_total"][()]
                        for s in range(ns))
                    assert worker_push == server_recv, (worker_push,
                                                        server_recv)
                    parity = [worker_push, server_recv]
            print(json.dumps({
                "digest": digest.hexdigest(),
                "quant_wire": snap.get("bps_quant_bytes_on_wire_total",
                                       0),
                "quant_saved": snap.get("bps_quant_bytes_saved_total",
                                        0),
                "push_bytes": snap.get("bps_push_bytes_total", 0),
                "push_partitions": snap.get("bps_push_partitions_total",
                                            0),
                "fused": snap.get("bps_fused_msgs_total", 0),
                "retries": snap.get("bps_retries_total", 0),
                "chaos_injected": snap.get("bps_chaos_injected_total",
                                           0),
                # Wire integrity (ISSUE 19) composition evidence: CRC
                # verification failures this rank detected itself.
                "crc_fails": snap.get("bps_crc_fail_total", 0),
                "parity": parity,
                # Round-insight composition evidence (ISSUE 7).
                "rounds_completed": snap.get(
                    "bps_rounds_completed_total", 0),
                "sched_fleet_workers": sched_fleet_workers,
            }), flush=True)
            # Hold the fleet until rank 0 finished scraping everyone.
            w.barrier(GROUP_WORKERS)

        elif mode == "chaos":
            # Transient-fault tolerance acceptance (ISSUE 3): a
            # multi-round, many-tensor training-shaped workload that the
            # parent runs twice — chaos on (drop + dup + reset, fixed
            # seed) and chaos off — and diffs. Integer-valued floats make
            # the summation exact, so the digests must match BITWISE:
            # every injected fault must be absorbed by retry/dedup/
            # reconnect without double-applying a single push. Broadcast
            # is included so the BCAST dedup paths are exercised too.
            # Synchronous step pattern (wait each round), like real
            # training — deep pipelining is outside the replay window's
            # contract (docs/troubleshooting.md).
            import json
            import hashlib

            sizes = [64, 96, 128, 192, 256, 384, 512, 768, 1024,
                     1536] * 3  # 30 tensors, 256 B .. 6 KiB
            tids = [w.declare(f"ch{i}", n, "float32", compression="")
                    for i, n in enumerate(sizes)]
            # Seed round: root broadcasts a known pattern.
            bc = w.declare("ch_bc", 512, "float32", compression="")
            arr_bc = (np.arange(512, dtype=np.float32) if rank == 0
                      else np.zeros(512, np.float32))
            w.wait(w.broadcast(bc, arr_bc, root_rank=0))
            np.testing.assert_array_equal(
                arr_bc, np.arange(512, dtype=np.float32))
            digest = hashlib.sha256()
            digest.update(arr_bc.tobytes())
            scale = sum(r + 1 for r in range(nw))
            for rnd in range(4):
                staged = []
                for i, (tid, n) in enumerate(zip(tids, sizes)):
                    base = (np.arange(n) % 89 + i + rnd + 1).astype(
                        np.float32)
                    arr = np.ascontiguousarray(base * (rank + 1))
                    staged.append((w.push_pull(tid, arr, average=False),
                                   arr, base))
                for h, arr, base in staged:
                    w.wait(h)
                    np.testing.assert_array_equal(arr, base * scale)
                    digest.update(arr.tobytes())
            w.barrier(GROUP_WORKERS)  # all counters final
            snap = w.metrics_snapshot()["counters"]
            print(json.dumps({
                "digest": digest.hexdigest(),
                "retries": snap.get("bps_retries_total", 0),
                "reconnects": snap.get("bps_reconnects_total", 0),
                "chaos_injected": snap.get("bps_chaos_injected_total", 0),
                "chaos_drop": snap.get("bps_chaos_drop_total", 0),
                "chaos_dup": snap.get("bps_chaos_dup_total", 0),
                "chaos_reset": snap.get("bps_chaos_reset_total", 0),
                # Wire integrity (ISSUE 19): this rank's own receive-side
                # CRC accounting. Under BYTEPS_CHAOS_CORRUPT the servers
                # corrupt their replies too, so the worker's own
                # crc_fails proves end-to-end verification, not just
                # server-side.
                "chaos_corrupt": snap.get("bps_chaos_corrupt_total", 0),
                "crc_fails": snap.get("bps_crc_fail_total", 0),
                "crc_quarantines": snap.get(
                    "bps_crc_quarantine_total", 0),
                "push_partitions": snap.get("bps_push_partitions_total",
                                            0),
                "push_bytes": snap.get("bps_push_bytes_total", 0),
            }), flush=True)
            w.barrier(GROUP_WORKERS)

        elif mode == "recovery":
            # Hot server replacement acceptance (ISSUE 4): a chaos-style
            # multi-round, many-tensor run — integer-valued floats, so
            # summation is exact and digests compare BITWISE across
            # runs — paced so the parent can SIGKILL a server mid-round
            # and respawn it with DMLC_RECOVER_RANK. The run must
            # complete with the same digest as the fault-free run, and
            # the counters prove a recovery actually happened.
            import hashlib
            import json
            import time as _t

            sizes = [64, 96, 128, 192, 256, 384, 512, 768, 1024,
                     1536] * 3  # 30 tensors, 256 B .. 6 KiB
            tids = [w.declare(f"rc{i}", n, "float32", compression="")
                    for i, n in enumerate(sizes)]
            bc = w.declare("rc_bc", 512, "float32", compression="")
            arr_bc = (np.arange(512, dtype=np.float32) if rank == 0
                      else np.zeros(512, np.float32))
            w.wait(w.broadcast(bc, arr_bc, root_rank=0))
            np.testing.assert_array_equal(
                arr_bc, np.arange(512, dtype=np.float32))
            digest = hashlib.sha256()
            digest.update(arr_bc.tobytes())
            scale = sum(r + 1 for r in range(nw))
            rounds = int(os.environ.get("BPS_TEST_ROUNDS", "8"))
            sleep_s = float(os.environ.get("BPS_TEST_ROUND_SLEEP", "0.3"))
            # Under the quantized wire (ISSUE 6 recovery composition)
            # aggregates are exact-to-EF-tolerance rather than exact;
            # the DIGEST stays the bit-identity oracle across the
            # fault-free / kill-one-server variants.
            quant_on = os.environ.get(
                "BYTEPS_WIRE_QUANT", "") not in ("", "0")
            for rnd in range(rounds):
                staged = []
                for i, (tid, n) in enumerate(zip(tids, sizes)):
                    base = (np.arange(n) % 89 + i + rnd + 1).astype(
                        np.float32)
                    arr = np.ascontiguousarray(base * (rank + 1))
                    staged.append((w.push_pull(tid, arr, average=False),
                                   arr, base))
                for h, arr, base in staged:
                    w.wait(h)
                    if quant_on:
                        expect = base * scale
                        tol = float(np.abs(expect).max()) * 0.03 + 1e-3
                        np.testing.assert_allclose(arr, expect, rtol=0,
                                                   atol=tol)
                    else:
                        np.testing.assert_array_equal(arr, base * scale)
                    digest.update(arr.tobytes())
                print(f"round {rnd}", flush=True)
                _t.sleep(sleep_s)
            w.barrier(GROUP_WORKERS)  # all counters final
            snap = w.metrics_snapshot()
            print(json.dumps({
                "digest": digest.hexdigest(),
                "recoveries": snap["counters"].get(
                    "bps_recoveries_total", 0),
                "epoch": snap["gauges"].get("bps_membership_epoch", 0),
                "retries": snap["counters"].get("bps_retries_total", 0),
                "reconnects": snap["counters"].get(
                    "bps_reconnects_total", 0),
                "chaos_injected": snap["counters"].get(
                    "bps_chaos_injected_total", 0),
                "sched_recoveries": snap["counters"].get(
                    "bps_sched_recoveries_total", 0),
            }), flush=True)
            w.barrier(GROUP_WORKERS)

        elif mode == "trace_fleet":
            # Fleet-tracing acceptance (ISSUE 5): a multi-round small-
            # tensor run with BYTEPS_TRACE_ON=1. Every role auto-dumps
            # its per-rank timeline at shutdown; the parent test merges
            # them (monitor.timeline) and checks flow stitching + that
            # the critical-path stage totals agree with this worker's
            # /metrics histograms, printed here from the same registry.
            import json
            sizes = [64, 128, 256, 512, 1024, 2048] * 4  # 24 tensors
            tids = [w.declare(f"tf{i}", n, "float32", compression="")
                    for i, n in enumerate(sizes)]
            for rnd in range(3):
                staged = []
                for i, (tid, n) in enumerate(zip(tids, sizes)):
                    base = (np.arange(n) % 31 + i + rnd + 1).astype(
                        np.float32)
                    arr = np.ascontiguousarray(base * (rank + 1))
                    staged.append((w.push_pull(tid, arr, average=False),
                                   arr, base))
                scale = sum(r + 1 for r in range(nw))
                for h, arr, base in staged:
                    w.wait(h)
                    np.testing.assert_array_equal(arr, base * scale)
            w.barrier(GROUP_WORKERS)  # all histograms final
            snap = w.metrics_snapshot()
            histos = snap["histograms"]
            print(json.dumps({
                "node_id": snap["node"]["id"],
                "push_us_sum": histos["bps_push_us"]["sum"],
                "push_count": histos["bps_push_us"]["count"],
                "pull_us_sum": histos["bps_pull_us"]["sum"],
                "trace_events": snap["counters"].get(
                    "bps_trace_events_total", 0),
                "trace_dropped": snap["counters"].get(
                    "bps_trace_dropped_total", 0),
            }), flush=True)
            w.barrier(GROUP_WORKERS)

        elif mode == "ckpt":
            # Durable-checkpoint acceptance (ISSUE 18): a state-
            # recurrent training loop where each round's push is a
            # deterministic integer-float function of the PREVIOUS
            # round's aggregate — so the full trajectory is recoverable
            # from any one committed round, and bit-identity of the
            # per-round digests proves the restored state byte-exact.
            #   round r: push (state % 97 + 1) * (rank+1); the summed
            #   aggregate becomes the next state. Fresh runs start from
            #   a fixed base; a RESTORED run reconstructs state by
            #   pulling the fleet-committed restore cut (version R)
            #   from the servers' snapshot endpoints — worker state
            #   comes FROM the restored servers, never from anything
            #   that survived the crash locally.
            import hashlib
            import json
            import time as _t

            from byteps_tpu.core.ffi import restore_round

            sizes = [64, 96, 128, 192, 256, 384, 512, 768, 1024,
                     1536] * 3  # 30 tensors, 256 B .. 6 KiB
            total = int(os.environ.get("BPS_TEST_ROUNDS", "12"))
            sleep_s = float(os.environ.get("BPS_TEST_ROUND_SLEEP", "0"))
            tids = [w.declare(f"ck{i}", n, "float32", compression="")
                    for i, n in enumerate(sizes)]
            scale = sum(r + 1 for r in range(nw))
            bases = [(np.arange(n) % 23 + i + 1).astype(np.float32)
                     for i, n in enumerate(sizes)]
            R = restore_round()
            if R >= 0 and os.environ.get("BPS_TEST_SNAP_ADDRS"):
                # The declares above made every shard install + publish
                # its restored aggregates at round R; pull that one
                # committed cut (pinned, raw float32) as our state.
                from byteps_tpu.client import SnapshotClient
                addrs = os.environ["BPS_TEST_SNAP_ADDRS"].split(",")
                keys = [tid << 16 for tid in tids]
                # Short per-request timeout: a chaos-dropped serving
                # reply must cost one quick failover, not a 30 s stall.
                with SnapshotClient(endpoints=addrs, quant=False,
                                    timeout=3.0) as c:
                    version, vals = c.pull(keys, version=R)
                assert version == R, (version, R)
                states = [vals[k].copy() for k in keys]
                for i, st in enumerate(states):
                    assert st.shape == (sizes[i],), (i, st.shape)
                start = R + 1
            elif R >= 0:
                # Restored fleet but no serving endpoints to rebuild
                # worker state from (launcher-level escalation tests):
                # resume the round counters at the restore cut with
                # fresh base state. Digest bit-identity is only claimed
                # by the tests that DO pull the cut.
                states = [b.copy() for b in bases]
                start = R + 1
            else:
                states = [b.copy() for b in bases]
                start = 0
            # Die-once hook: rank 0 simulates a mid-run preemption at
            # the given round on its FIRST life (marker file), so a
            # launcher --restarts relaunch can prove the escalation to
            # restore mode end to end.
            die_at = int(os.environ.get("BPS_TEST_DIE_AT_ROUND", "-1"))
            die_marker = os.environ.get("BPS_TEST_DIE_MARKER", "")
            digests = {}
            for rnd in range(start, total):
                if (rnd == die_at and rank == 0 and die_marker
                        and not os.path.exists(die_marker)):
                    with open(die_marker, "w") as f:
                        f.write("died\n")
                    print("simulating full-fleet preemption", flush=True)
                    os._exit(1)
                staged = []
                for i, tid in enumerate(tids):
                    arr = np.ascontiguousarray(
                        (states[i] % 97 + 1) * (rank + 1))
                    staged.append((w.push_pull(tid, arr, average=False),
                                   arr, i))
                dg = hashlib.sha256()
                for h, arr, i in staged:
                    w.wait(h)
                    states[i] = arr.copy()
                    dg.update(arr.tobytes())
                digests[rnd] = dg.hexdigest()
                print(f"round {rnd}", flush=True)
                if sleep_s:
                    _t.sleep(sleep_s)
            w.barrier(GROUP_WORKERS)
            snap = w.metrics_snapshot()["counters"]
            print(json.dumps({
                "digests": digests,
                "restore_round": R,
                "retries": snap.get("bps_retries_total", 0),
                "chaos_injected": snap.get("bps_chaos_injected_total",
                                           0),
            }), flush=True)
            w.barrier(GROUP_WORKERS)

        elif mode == "barrier":
            w.barrier(GROUP_WORKERS)
            print(f"rank {rank} passed barrier")

        else:
            raise SystemExit(f"unknown BPS_TEST_MODE {mode!r}")

        # Which transport this process's dialled connections got (the
        # van's counters): the transport tests assert it from here and
        # from the van's DEBUG line, so a silent fallback fails them.
        import json as _json
        snap = w.metrics_snapshot()["counters"]
        print("van_conns " + _json.dumps({
            k: snap.get(f"bps_van_{n}_total", 0) for k, n in
            (("shm", "conns_shm"), ("tcp", "conns_tcp"),
             ("fallback", "shm_fallback"))}), flush=True)
        print(f"worker {rank}: {mode} OK")
        return 0
    finally:
        w.shutdown()


def jax_train_main() -> int:
    """End-to-end: PS-mode DP training across worker processes must match
    single-process training on the combined batch (jax plugin owns the
    BytePS worker; do not Worker.start() separately)."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import optax
    import byteps_tpu.jax as bps_jax
    from byteps_tpu.config import get_config
    from byteps_tpu.jax.training import make_train_step

    cfg = get_config(reload=True)
    assert cfg.use_ps, "expected PS mode in jax_train"
    bps_jax.init()
    st = bps_jax._st()
    assert st.ps_client is not None
    rank = st.ps_client.worker_rank()
    nw = st.ps_client.num_workers()

    def loss_fn(params, batch):
        x, y = batch
        pred = jnp.tanh(x @ params["w1"]) @ params["w2"]
        return jnp.mean((pred - y) ** 2)

    prng = np.random.default_rng(5)
    params0 = {
        "w1": jnp.asarray(prng.standard_normal((6, 8)), jnp.float32) * 0.4,
        "w2": jnp.asarray(prng.standard_normal((8, 3)), jnp.float32) * 0.4,
    }
    tx = optax.sgd(0.1)
    step = make_train_step(loss_fn, tx)
    params = jax.tree_util.tree_map(jnp.array, params0)
    opt_state = tx.init(params)
    per = 8  # rows per worker
    for _ in range(6):
        gx = prng.standard_normal((nw * per, 6)).astype(np.float32)
        gy = gx[:, :3] * 2.0
        lo, hi = rank * per, (rank + 1) * per
        params, opt_state, loss = step(params, opt_state,
                                       (gx[lo:hi], gy[lo:hi]))

    # reference: replay the same stream, full global batch, one device
    ref_prng = np.random.default_rng(5)
    ref_prng.standard_normal((6, 8))
    ref_prng.standard_normal((8, 3))

    @jax.jit
    def ref_step(p, s, batch):
        _, g = jax.value_and_grad(loss_fn)(p, batch)
        u, s = tx.update(g, s, p)
        return optax.apply_updates(p, u), s

    ref_params = jax.tree_util.tree_map(jnp.array, params0)
    ref_state = tx.init(ref_params)
    for _ in range(6):
        gx = ref_prng.standard_normal((nw * per, 6)).astype(np.float32)
        gy = gx[:, :3] * 2.0
        ref_params, ref_state = ref_step(ref_params, ref_state, (gx, gy))
    for k in params:
        np.testing.assert_allclose(
            np.asarray(params[k]), np.asarray(ref_params[k]),
            rtol=2e-4, atol=2e-5)
    bps_jax.shutdown()
    print(f"worker {rank}: jax_train OK")
    return 0


def jax_async_main() -> int:
    """Async PS training (BYTEPS_ENABLE_ASYNC): no per-round barrier,
    server-resident accumulator. Assert convergence, not bitwise parity —
    staleness is the contract (reference: server.cc async mode)."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import optax
    import byteps_tpu.jax as bps_jax
    from byteps_tpu.config import get_config

    cfg = get_config(reload=True)
    assert cfg.use_ps and cfg.enable_async
    bps_jax.init()
    try:
        from byteps_tpu.jax.training import make_async_train_step

        rank = bps_jax._st().ps_client.worker_rank()

        def loss_fn(params, batch):
            x, y = batch
            return jnp.mean((x @ params["w"] - y) ** 2)

        prng = np.random.default_rng(11)
        w_true = prng.standard_normal((6, 3)).astype(np.float32)
        params = {"w": jnp.zeros((6, 3), jnp.float32)}
        tx = optax.sgd(0.05)
        params, step = make_async_train_step(loss_fn, tx, params)
        opt_state = tx.init(params)
        first = last = None
        for i in range(40):
            x = prng.standard_normal((16, 6)).astype(np.float32)
            y = x @ w_true
            params, opt_state, loss = step(params, opt_state, (x, y))
            if first is None:
                first = float(loss)
            last = float(loss)
        assert last < first * 0.2, (first, last)
        print(f"worker {rank}: jax_async OK ({first:.4f} -> {last:.4f})")
        return 0
    finally:
        bps_jax.shutdown()


def jax_async_seed_main() -> int:
    """Regression for the async seeding key mismatch: make_async_train_step
    seeds the server copy via ps_broadcast's `{prefix}_{crc32:08x}_{i}`
    wire keys, and the step's delta pushes MUST land on those same keys.
    With the old bare `{prefix}_{i}` declares the first delta silently
    BECAME the parameters: one SGD step from w=1.0 with grad -4 and
    lr 0.1 returned 0.4 instead of 1.4."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import optax
    import byteps_tpu.jax as bps_jax
    from byteps_tpu.config import get_config

    cfg = get_config(reload=True)
    assert cfg.use_ps and cfg.enable_async
    bps_jax.init()
    try:
        from byteps_tpu.jax.training import make_async_train_step

        rank = bps_jax._st().ps_client.worker_rank()

        def loss_fn(params, batch):
            # d(loss)/dw == -4 everywhere; batch is just along for the API
            return -4.0 * jnp.sum(params["w"]) + 0.0 * jnp.sum(batch)

        params = {"w": jnp.asarray([1.0], jnp.float32)}
        tx = optax.sgd(0.1)
        params, step = make_async_train_step(loss_fn, tx, params)
        opt_state = tx.init(params)
        np.testing.assert_allclose(np.asarray(params["w"]), 1.0)
        params, opt_state, _ = step(params, opt_state,
                                    jnp.zeros((1,), jnp.float32))
        got = float(np.asarray(params["w"])[0])
        assert abs(got - 1.4) < 1e-6, (
            f"async step from w=1.0, grad -4, lr 0.1 must pull 1.4 "
            f"(seeded params + delta); got {got} — the delta keys missed "
            "the broadcast-seeded server tensors")
        print(f"worker {rank}: jax_async_seed OK (w=1.0 -> {got})")
        return 0
    finally:
        bps_jax.shutdown()


def jax_bridge_main() -> int:
    """Host-boundary discipline of the JAX<->PS bridge: declares are
    cached for the tree's lifetime (one registration per tensor, not one
    per step) and repeated steps stay numerically exact. Prints the
    steady-state bridge step time as a microbenchmark line."""
    import time

    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import byteps_tpu.jax as bps_jax
    from byteps_tpu.config import get_config
    from byteps_tpu.jax import ps as ps_mod

    cfg = get_config(reload=True)
    assert cfg.use_ps
    bps_jax.init()
    try:
        client = bps_jax._st().ps_client
        nw = client.num_workers()
        rank = client.worker_rank()
        # Many small leaves — the shape where per-step declare/ctypes
        # churn dominated before tid caching.
        tree = {f"w{i}": jnp.full((257,), float(rank + 1), jnp.float32)
                for i in range(64)}
        expect = sum(r + 1 for r in range(nw))
        t0 = time.perf_counter()
        steps = 20
        for _ in range(steps):
            out = ps_mod.ps_push_pull(tree, average=False, prefix="br")
        dt = (time.perf_counter() - t0) / steps
        assert ps_mod.declare_steps == 1, (
            f"declares must be cached: {ps_mod.declare_steps} declare "
            "rounds for a fixed tree")
        for leaf in jax.tree_util.tree_leaves(out):
            np.testing.assert_allclose(np.asarray(leaf), expect, rtol=1e-6)
        print(f"worker {rank}: jax_bridge OK "
              f"({dt * 1e3:.2f} ms/step, 64 leaves x 257 f32)")
        return 0
    finally:
        bps_jax.shutdown()


def jax_stream_main() -> int:
    """The streamed ``ps_push_pull`` (a leaf enqueued as it lands, put back
    as its handle settles) returns bit for bit the sum numpy computes from
    every worker's values: three rounds over one tree of device arrays —
    vectors, a scalar, a matrix and a last leaf of several partitions —
    summed and averaged, every round with values of its own, so nothing of
    round n may survive in a slot into round n + 1. A leaf in its wire form
    goes to the C core as source = the device array's own read-only host
    array, destination = the slot (on this backend the source is the device
    buffer itself: bit for bit what it was after the round, or jax's
    immutable array was written); any other as one buffer, in place.
    ``stage_stats``: a prefix's first call pulls into new buffers, every
    later one into the slots of the call before — all of them, but for a
    slot whose upload the CPU backend made of the buffer itself (an aligned
    one), which went with that result; which those are is read here from
    the pointers the C core was handed and each upload's own.
    BPS_STREAM_CASE: ``f32``; ``bf16_codec`` (bfloat16 leaves under a
    configured codec — here a top-k that keeps every element, so the wire
    is exact — upcast into float32 slots, pushed from there and put back as
    bfloat16); ``int_leaf`` (an int32 counter among the floats). Two
    workers, so the server's sum has one order."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import byteps_tpu.jax as bps_jax
    from byteps_tpu.jax import ps as ps_mod

    case = os.environ["BPS_STREAM_CASE"]
    dtype = jnp.dtype("bfloat16" if case == "bf16_codec" else "float32")
    shapes = [(7,), (), (33, 5), (257,), (1_500_000,)]

    def values(rank, step):
        rng = np.random.default_rng(1000 * step + rank)
        tree = {f"l{i}": rng.standard_normal(s).astype(np.float32).astype(
            dtype) for i, s in enumerate(shapes)}
        if case != "f32":
            tree["count"] = np.asarray(step * 10 + rank + 1, np.int32)
        return tree

    bps_jax.init()
    try:
        client = bps_jax._st().ps_client
        nw, rank = client.num_workers(), client.worker_rank()
        assert nw == 2
        handed, aliased, real_push_pull = [], set(), client.push_pull
        real_put = jax.device_put

        def push_pull(tid, arr, out=None, **kwargs):  # what the core gets
            assert out.flags.writeable and out.flags.c_contiguous
            if out is not arr:  # pushed from where it landed, never written
                assert not arr.flags.writeable
                assert not np.shares_memory(arr, out)
            handed.append((out.ctypes.data, out.nbytes, arr))
            return real_push_pull(tid, arr, out=out, **kwargs)

        def device_put(x):  # and which uploads ARE the host buffer
            dev = real_put(x)
            if dev.unsafe_buffer_pointer() == x.ctypes.data:
                aliased.add(x.ctypes.data)
            return dev

        client.push_pull, jax.device_put = push_pull, device_put
        kept = {}  # prefix -> bytes its next call must find in the pool
        for step in range(3):
            mine = values(rank, step)
            for average in (False, True):
                del handed[:]
                aliased.clear()
                out = ps_mod.ps_push_pull(
                    jax.tree_util.tree_map(jnp.asarray, mine),
                    average=average, prefix=f"st{int(average)}")
                staged = sum(n for _, n, _ in handed)
                # every leaf with an axis that is in its wire dtype
                direct = [np.asarray(v) for v in mine.values()
                          if v.shape and (case != "bf16_codec"
                                          or v.dtype != dtype)]
                sources = [a for p, _, a in handed if a.ctypes.data != p]
                assert len(sources) == len(direct)
                for got, want in zip(sources, direct):  # as they went in
                    np.testing.assert_array_equal(got, want)
                assert ps_mod.stage_stats == {
                    "direct_bytes": sum(a.nbytes for a in direct),
                    "reused_bytes": kept.get(average, 0),
                    "bytes": staged}, (step, average, ps_mod.stage_stats)
                kept[average] = sum(n for ptr, n, _ in handed
                                    if ptr not in aliased)
                assert step == 0 or case != "bf16_codec" or (
                    ps_mod.stage_stats["reused_bytes"] >= staged - 4), (
                        "a float32 slot of a bfloat16 leaf is never uploaded")
                theirs = [values(r, step) for r in range(nw)]
                for name, got in out.items():
                    a, b = (t[name] for t in theirs)
                    wide = np.float32 if a.dtype == dtype else a.dtype
                    want = a.astype(wide) + b.astype(wide)
                    if average:
                        want = want / nw if wide == np.float32 else want // nw
                    want = want.astype(a.dtype)
                    assert isinstance(got, jax.Array), type(got)
                    assert got.dtype == want.dtype and got.shape == want.shape
                    np.testing.assert_array_equal(
                        np.asarray(got), want,
                        err_msg=f"{case} step {step} avg {average} {name}")
                nbytes = [v.nbytes for v in jax.tree_util.tree_leaves(mine)]
                assert ps_mod.put_stats == {
                    "put_early_bytes": sum(nbytes[:-1]),
                    "bytes": sum(nbytes)}, ps_mod.put_stats
        print(f"worker {rank}: jax_stream {case} OK")
        return 0
    finally:
        bps_jax.shutdown()


def jax_global_main() -> int:
    """Horovod-global semantics of the BARE jax-level API in PS mode: a
    user's ``bps.push_pull`` / ``bps.broadcast_parameters`` at host level
    must cross the worker fleet through the servers, not silently reduce
    over this process's chips only (round-5 regression: the host-level
    path used to skip the DCN leg)."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import byteps_tpu.jax as bps_jax

    bps_jax.init()
    try:
        client = bps_jax._st().ps_client
        assert client is not None
        rank, nw = client.worker_rank(), client.num_workers()
        n_dev = bps_jax._st().mesh.size

        # push_pull: stacked over local devices, summed across the fleet
        for i in range(2):
            x = jnp.full((n_dev, 1000), float(rank + 1), jnp.float32)
            out = bps_jax.push_pull(x, average=False, name=f"g{i}")
            expect = n_dev * sum(r + 1 for r in range(nw))
            np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-6)
        # average=True: global mean over n_dev x nw replicas
        x = jnp.full((n_dev, 64), float(rank + 1), jnp.float32)
        out = bps_jax.push_pull(x, average=True, name="gavg")
        expect = sum(r + 1 for r in range(nw)) / nw
        np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-6)

        # unnamed calls with DIFFERENT tree shapes must not collide in
        # the PS registry (shape-keyed wire names, not a fatal re-declare)
        a = bps_jax.push_pull(jnp.full((n_dev, 16), float(rank + 1)),
                              average=False)
        b = bps_jax.push_pull(jnp.full((n_dev, 48), float(rank + 1)),
                              average=False)
        expect = n_dev * sum(r + 1 for r in range(nw))
        np.testing.assert_allclose(np.asarray(a), expect, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(b), expect, rtol=1e-6)

        # async handles: immediate return, poll converges, result exact
        h = bps_jax.push_pull_async(
            jnp.full((n_dev, 256), float(rank + 1), jnp.float32),
            average=False, name="ah")
        out = bps_jax.synchronize(h)
        assert bps_jax.poll(h)
        np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-6)

        # broadcast_parameters: every worker ends with rank 0's values
        val = (np.arange(500, dtype=np.float32) if rank == 0
               else np.zeros(500, np.float32))
        tree = {"w": jnp.asarray(val)}
        tree = bps_jax.broadcast_parameters(tree, root_rank=0)
        np.testing.assert_allclose(np.asarray(tree["w"]),
                                   np.arange(500, dtype=np.float32))

        # broadcast_optimizer_state: arrays sync, python scalars pass
        opt = {"mu": jnp.full((37,), float(rank)), "count": 7,
               "nu": jnp.full((11,), float(rank * 2))}
        opt = bps_jax.broadcast_optimizer_state(opt, root_rank=0)
        np.testing.assert_allclose(np.asarray(opt["mu"]), 0.0)
        np.testing.assert_allclose(np.asarray(opt["nu"]), 0.0)
        assert opt["count"] == 7
        print(f"worker {rank}: jax_global OK")
        return 0
    finally:
        bps_jax.shutdown()


def mxnet_stub_main() -> int:
    """Execute the REAL byteps_tpu.mxnet plugin over the REAL PS topology,
    with only the (uninstallable, EOL) mxnet package emulated by the
    API-faithful stub in tests/mxnet_stub.py. Covers push_pull numerics,
    broadcast_parameters, and DistributedTrainer's reduce+rescale step."""
    import mxnet_stub
    sys.modules["mxnet"] = mxnet_stub
    sys.modules["mxnet.gluon"] = mxnet_stub.gluon

    import byteps_tpu.mxnet as bps_mx
    from mxnet_stub import NDArray, gluon

    bps_mx.init()
    try:
        rank, nw = bps_mx.rank(), bps_mx.size()
        rng2 = np.random.default_rng(21)

        # push_pull: in-place sum and average across workers
        base = rng2.standard_normal(48).astype(np.float32)
        t = NDArray(base * (rank + 1))
        bps_mx.byteps_push_pull(t, name="mx_t0", is_average=False)
        scale = sum(r + 1 for r in range(nw))
        np.testing.assert_allclose(t.asnumpy(), base * scale, rtol=1e-5)
        t2 = NDArray(np.full(16, float(rank + 1), np.float32))
        bps_mx.byteps_push_pull(t2, name="mx_t1", is_average=True)
        np.testing.assert_allclose(t2.asnumpy(), scale / nw, rtol=1e-6)

        # broadcast_parameters from root
        val = (rng2.standard_normal(10).astype(np.float32)
               if rank == 0 else np.zeros(10, np.float32))
        params = {"w": NDArray(val)}
        bps_mx.broadcast_parameters(params, root_rank=0)
        # replay rank 0's RNG stream to know what it broadcast
        root_stream = np.random.default_rng(21)
        root_stream.standard_normal(48)
        expect_w = root_stream.standard_normal(10).astype(np.float32)
        np.testing.assert_allclose(params["w"].asnumpy(), expect_w,
                                   rtol=1e-6)

        # DistributedTrainer: server-side SUM + _scale/=size == average
        w0 = np.ones(8, np.float32)
        p = gluon.Parameter("w", w0.copy())
        tr = bps_mx.DistributedTrainer(
            [p], "sgd", {"learning_rate": 0.5})
        g = np.full(8, float(rank + 1), np.float32)
        p.set_grad(g)
        tr.step(batch_size=1)
        mean_grad = scale / nw
        np.testing.assert_allclose(
            p.data().asnumpy(), w0 - 0.5 * mean_grad, rtol=1e-6)

        print(f"worker {rank}: mxnet_stub OK")
        return 0
    finally:
        bps_mx.shutdown()


def jax_timeline_main() -> int:
    """Combined device+DCN timeline from a REAL training step: the
    Timeline helper captures jax.profiler over the trace window, drains
    the C core's push/pull spans, and merges both into one Chrome JSON
    (SURVEY.md §5 XPlane interop)."""
    import json

    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import optax
    import byteps_tpu.jax as bps_jax
    from byteps_tpu.config import get_config
    from byteps_tpu.jax.training import make_train_step
    from byteps_tpu.utils import Timeline

    cfg = get_config(reload=True)
    assert cfg.use_ps and cfg.trace_on
    bps_jax.init()
    try:
        rank = bps_jax._st().ps_client.worker_rank()

        def loss_fn(params, batch):
            x, y = batch
            return jnp.mean((x @ params["w"] - y) ** 2)

        tx = optax.sgd(0.05)
        step = make_train_step(loss_fn, tx)
        params = {"w": jnp.zeros((64, 8), jnp.float32)}
        opt_state = tx.init(params)
        tl = Timeline()
        prng = np.random.default_rng(3)
        for _ in range(cfg.trace_end_step + 1):
            x = jnp.asarray(prng.standard_normal((16, 64)), jnp.float32)
            y = x[:, :8] * 0.5
            params, opt_state, loss = step(params, opt_state, (x, y))
            tl.step()
        tl.close()
        combined = os.path.join(cfg.trace_dir, f"combined_rank{rank}.json")
        assert os.path.exists(combined), "combined timeline not written"
        with open(combined) as f:
            evs = json.load(f)["traceEvents"]
        names = {e.get("name") for e in evs}
        assert "push" in names and "pull" in names, names
        dcn = [e for e in evs if e.get("pid") == 900000 and "ts" in e]
        dev = [e for e in evs if e.get("pid") != 900000 and "ts" in e]
        assert dcn and dev, (len(dcn), len(dev))
        print(f"worker {rank}: jax_timeline OK "
              f"({len(dev)} device events + {len(dcn)} DCN spans merged)")
        return 0
    finally:
        bps_jax.shutdown()


def jax_bucketed_main() -> int:
    """The overlap step — bucketed multi-program stepping (SURVEY.md §7
    hard part #1) — must reproduce single-process numerics: per-bucket
    gradient programs + the D2H/DCN/H2D bucket pipeline change WHEN
    communication happens, never WHAT is summed."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import optax
    import byteps_tpu.jax as bps_jax
    from byteps_tpu.jax.bucketed import make_bucketed_overlap_step

    bps_jax.init()
    try:
        st = bps_jax._st()
        rank = st.ps_client.worker_rank()
        nw = st.ps_client.num_workers()

        def loss_fn(params, batch):
            x, y = batch
            h = jnp.tanh(x @ params["w1"] + params["b1"])
            pred = h @ params["w2"]
            return jnp.mean((pred - y) ** 2)

        prng = np.random.default_rng(5)
        params0 = {
            "w1": jnp.asarray(prng.standard_normal((6, 8)),
                              jnp.float32) * 0.4,
            "b1": jnp.zeros((8,), jnp.float32),
            "w2": jnp.asarray(prng.standard_normal((8, 3)),
                              jnp.float32) * 0.4,
        }
        tx = optax.sgd(0.1)
        wire = os.environ.get("BPS_OVERLAP_WIRE") or "float32"
        comp = os.environ.get("BPS_OVERLAP_COMPRESSION") or None
        step = make_bucketed_overlap_step(
            loss_fn, tx, n_buckets=int(os.environ.get("BPS_BUCKET_N", "2")),
            wire_dtype=wire, compression_config=comp)
        params = jax.tree_util.tree_map(jnp.array, params0)
        opt_state = tx.init(params)
        per = 8
        for _ in range(6):
            gx = prng.standard_normal((nw * per, 6)).astype(np.float32)
            gy = gx[:, :3] * 2.0
            lo, hi = rank * per, (rank + 1) * per
            params, opt_state, loss = step(params, opt_state,
                                           (gx[lo:hi], gy[lo:hi]))

        ref_prng = np.random.default_rng(5)
        ref_prng.standard_normal((6, 8))
        ref_prng.standard_normal((8, 3))

        @jax.jit
        def ref_step(p, s, batch):
            _, g = jax.value_and_grad(loss_fn)(p, batch)
            u, s = tx.update(g, s, p)
            return optax.apply_updates(p, u), s

        ref_params = jax.tree_util.tree_map(jnp.array, params0)
        ref_state = tx.init(ref_params)
        for _ in range(6):
            gx = ref_prng.standard_normal((nw * per, 6)).astype(np.float32)
            gy = gx[:, :3] * 2.0
            ref_params, ref_state = ref_step(ref_params, ref_state,
                                             (gx, gy))
        if comp:
            rtol, atol = 0.5, 0.2
        elif wire == "bfloat16":
            rtol, atol = 0.05, 0.02
        else:
            rtol, atol = 2e-4, 2e-5
        for k in params:
            np.testing.assert_allclose(
                np.asarray(params[k]), np.asarray(ref_params[k]),
                rtol=rtol, atol=atol)
        print(f"worker {rank}: jax_bucketed OK (wire={wire})")
        return 0
    finally:
        bps_jax.shutdown()


if __name__ == "__main__":
    sys.exit(main())

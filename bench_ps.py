"""PS-mode benchmark on the chip: plain vs push_pull vs overlapped.

The reference's headline numbers are real-hardware PS-mode numbers
(SURVEY.md §3.3 hot path). This script runs the bench-host topology —
THIS process is the single TPU worker, and it self-provisions a
localhost fleet (scheduler + CPU server processes, which never import JAX
and so never touch the chip) — then measures, per model:

  plain          fused jitted train step, no sync framework (baseline)
  ps             make_train_step in PS mode: jit grad -> batched D2H ->
                 C-core push/pull over TCP -> H2D -> jit apply
  overlap        make_overlapped_train_step: per-parameter io_callback taps
                 stream pushes DURING backward (wire f32)
  overlap_bf16   same with in-jit bf16 wire cast (half the D2H bytes)

plus the host-boundary microbenchmarks the staging design rests on:
d2h_gbps / h2d_gbps for one gradient-sized transfer.

Prints one JSON line per measurement, each naming the platform,
device_kind and device count, and with --out writes the list as an
artifact. Steps/sec ratios are back-to-back per repeat (median ratio),
the drift-robust methodology from bench.py.

The main mode measures the TPU and refuses to run where JAX found none;
--smoke is the tiny-model CPU spelling (metrics named ``*_smoke_*``).

Run: python bench_ps.py --model resnet50 --out ps.json   (through the chip tool)
     (add --trace trace.json for a BYTEPS_TRACE_ON timeline capture)
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time


def _free_port() -> int:
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def provision_fleet(num_servers: int, trace_on: bool):
    """Spawn scheduler + servers; point THIS process at them as worker 0.

    One process per chip by construction: the children run
    ``python -m byteps_tpu.server``, which never imports JAX, so this
    process — the only one that touches JAX — is the only one that can
    hold the chip. Keep it so."""
    port = _free_port()
    base = {
        "DMLC_PS_ROOT_URI": "127.0.0.1",
        "DMLC_PS_ROOT_PORT": str(port),
        "DMLC_NUM_WORKER": "1",
        "DMLC_NUM_SERVER": str(num_servers),
    }
    procs = []
    for role, n in (("scheduler", 1), ("server", num_servers)):
        for _ in range(n):
            env = dict(os.environ)
            env.update(base)
            env["DMLC_ROLE"] = role
            env["PYTHONPATH"] = (os.path.dirname(os.path.abspath(__file__))
                                 + os.pathsep + env.get("PYTHONPATH", ""))
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "byteps_tpu.server"], env=env,
                stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT))
    os.environ.update(base)
    os.environ["DMLC_ROLE"] = "worker"
    os.environ["DMLC_WORKER_ID"] = "0"
    os.environ["BYTEPS_PS_MODE"] = "ps"
    os.environ["BYTEPS_FORCE_DISTRIBUTED"] = "1"
    if trace_on:
        os.environ["BYTEPS_TRACE_ON"] = "1"
    return procs


def _time_steps(step, state, batch, steps: int):
    """Seconds per step for step(*state, batch) -> (*state, loss)."""
    import jax
    state = step(*state, batch)   # warm / compile
    state = step(*state[:-1], batch)
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for _ in range(steps):
        state = step(*state[:-1], batch)
    jax.block_until_ready(state)
    return (time.perf_counter() - t0) / steps


def host_boundary_microbench(nbytes: int):
    """D2H / H2D GB/s for one contiguous f32 transfer of ``nbytes``
    (callers pass the model's gradient size). Returns (d2h, h2d, bytes
    actually moved)."""
    import jax
    import numpy as np
    n = nbytes // 4
    nbytes = n * 4  # what the probe actually moves; returned for the record
    reps = 2
    # One device array per repetition: a jax.Array keeps its host copy
    # after the first device_get, so a second get of the same array
    # would time a cache hit.
    make = jax.jit(lambda k: jax.random.normal(k, (n,)))
    devs = [make(jax.random.PRNGKey(i)) for i in range(reps)]
    jax.block_until_ready(devs)
    t0 = time.perf_counter()
    for dev in devs:
        host = jax.device_get(dev)
    d2h = nbytes * reps / (time.perf_counter() - t0)
    host = np.ascontiguousarray(host)
    t0 = time.perf_counter()
    for _ in range(reps):
        back = jax.device_put(host)
        jax.block_until_ready(back)
    h2d = nbytes * reps / (time.perf_counter() - t0)
    return d2h / 1e9, h2d / 1e9, nbytes


def build_model(name: str, batch: int, seq_len: int, smoke: bool):
    """Returns (loss_fn(params, batch)->scalar, params, batch_arrays,
    items_per_step, grad_bytes)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(0)
    if name == "resnet50":
        from byteps_tpu.jax.flax_util import cross_entropy_loss
        from byteps_tpu.models import ResNet18, ResNet50
        cls, img = (ResNet18, 64) if smoke else (ResNet50, 224)
        model = cls(num_classes=1000, dtype=jnp.bfloat16)
        x = jnp.asarray(rng.standard_normal((batch, img, img, 3)),
                        jnp.float32)
        y = jnp.asarray(rng.integers(0, 1000, batch), jnp.int32)
        variables = model.init(jax.random.PRNGKey(0), x[:1], train=False)
        stats = variables["batch_stats"]

        # BatchNorm statistics are computed from the batch in train mode
        # but their running-average update is discarded: all three paths
        # (plain / ps / overlap) then share one loss_fn(params, batch)
        # signature, so the comparison isolates gradient-sync cost.
        def loss_fn(p, b):
            bx, by = b
            out, _ = model.apply({"params": p, "batch_stats": stats}, bx,
                                 train=True, mutable=["batch_stats"])
            return cross_entropy_loss(out, by)

        params = variables["params"]
        data = (x, y)
        items = batch
    elif name == "gpt2":
        from byteps_tpu.models import GPT2Small, TransformerLM, lm_loss
        if smoke:
            model = TransformerLM(num_layers=2, d_model=128, num_heads=4,
                                  mlp_dim=256, vocab_size=1024, max_len=256,
                                  dtype=jnp.bfloat16)
        else:
            model = GPT2Small(dtype=jnp.bfloat16)
        toks = jnp.asarray(rng.integers(0, 1000, (batch, seq_len)),
                           jnp.int32)
        params = model.init(jax.random.PRNGKey(0), toks[:1])

        def loss_fn(p, b):
            return lm_loss(model.apply(p, b), b)

        data = toks
        items = batch
    else:
        raise SystemExit(f"unknown model {name!r}")

    grad_bytes = sum(
        int(np.size(l)) * 4 for l in jax.tree_util.tree_leaves(params))
    return loss_fn, params, data, items, grad_bytes


def _async_worker_main() -> int:
    """Worker body for --async-bench (spawned with BENCH_PS_ASYNC_WORKER
    set to sync|async). Trains the same seeded model either through the
    synchronous PS step (round windows: every worker's push completes the
    round, so ONE straggler paces the fleet) or the async step
    (server-resident params, no barrier — reference BYTEPS_ENABLE_ASYNC,
    whose whole pitch is throughput under skew)."""
    # A CPU fleet by design: run_async_bench pins the workers with the
    # JAX_PLATFORMS=cpu environment variable alone.
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import byteps_tpu.jax as bps
    from byteps_tpu.jax.training import (make_async_train_step,
                                         make_train_step)

    mode = os.environ["BENCH_PS_ASYNC_WORKER"]
    straggle = float(os.environ.get("BENCH_PS_STRAGGLE", "0"))
    steps = int(os.environ.get("BENCH_PS_ASYNC_STEPS", "60"))
    bps.init()
    st_ = bps._st()
    rank = st_.ps_client.worker_rank()

    rng = np.random.default_rng(7)
    params = {
        "w1": jnp.asarray(rng.standard_normal((16, 32)), jnp.float32) * .3,
        "b1": jnp.zeros((32,), jnp.float32),
        "w2": jnp.asarray(rng.standard_normal((32, 4)), jnp.float32) * .3,
    }
    X = rng.standard_normal((64, 16)).astype(np.float32)
    Y = np.tanh(X[:, :4]).astype(np.float32)

    def loss_fn(p, b):
        x, y = b
        return jnp.mean((jnp.tanh(x @ p["w1"] + p["b1"]) @ p["w2"] - y) ** 2)

    tx = optax.sgd(0.1)
    if mode == "async":
        params, step = make_async_train_step(loss_fn, tx, params)
    else:
        step = make_train_step(loss_fn, tx, donate=False)
    opt_state = tx.init(params)
    batch = (jnp.asarray(X), jnp.asarray(Y))

    for _ in range(3):  # warm / compile
        params, opt_state, loss = step(params, opt_state, batch)
    t0 = time.perf_counter()
    for _ in range(steps):
        if straggle and rank == 1:
            time.sleep(straggle)  # simulated slow compute on ONE worker
        params, opt_state, loss = step(params, opt_state, batch)
    wall = time.perf_counter() - t0
    final = float(loss_fn(params, batch))
    print(json.dumps({"rank": rank, "mode": mode,
                      "steps_per_sec": round(steps / wall, 3),
                      "wall_s": round(wall, 2),
                      "final_loss": round(final, 5)}), flush=True)
    bps.shutdown()
    return 0


def run_async_bench(args) -> None:
    """Async vs sync PS under a straggler (VERDICT r3 missing #3): same
    model, same data, same step count; worker 1 sleeps --straggle s per
    step. Reports worker 0's pace and both final losses per mode."""
    out = {"what": "async (server-resident params, no barrier) vs sync "
                   "(round windows) PS training under a straggler: "
                   "worker 1 sleeps the straggle before every step",
           "straggle_s": args.straggle, "steps": args.async_steps,
           "workers": 2, "modes": {}}
    for mode in ("sync", "async"):
        port = _free_port()
        base = {
            "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": str(port),
            "DMLC_NUM_WORKER": "2", "DMLC_NUM_SERVER": "1",
            "BYTEPS_ENABLE_ASYNC": "1" if mode == "async" else "0",
            "BYTEPS_PS_MODE": "ps", "BYTEPS_FORCE_DISTRIBUTED": "1",
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": (os.path.dirname(os.path.abspath(__file__))
                           + os.pathsep + os.environ.get("PYTHONPATH", "")),
        }
        procs, workers = [], []
        for role, n in (("scheduler", 1), ("server", 1)):
            for _ in range(n):
                env = dict(os.environ); env.update(base)
                env["DMLC_ROLE"] = role
                procs.append(subprocess.Popen(
                    [sys.executable, "-m", "byteps_tpu.server"], env=env,
                    stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT))
        for r in range(2):
            env = dict(os.environ); env.update(base)
            env.update({"DMLC_ROLE": "worker", "DMLC_WORKER_ID": str(r),
                        "BENCH_PS_ASYNC_WORKER": mode,
                        "BENCH_PS_ASYNC_STEPS": str(args.async_steps),
                        "BENCH_PS_STRAGGLE": str(args.straggle)})
            p = subprocess.Popen([sys.executable, os.path.abspath(__file__)],
                                 env=env, stdout=subprocess.PIPE,
                                 stderr=subprocess.STDOUT, text=True)
            procs.append(p); workers.append(p)
        rows = []
        try:
            for p in workers:
                sout, _ = p.communicate(timeout=600)
                if p.returncode != 0:
                    raise SystemExit(f"{mode} worker failed:\n{sout}")
                rows.extend(json.loads(ln) for ln in sout.splitlines()
                            if ln.startswith("{"))
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
        out["modes"][mode] = rows
        for r in rows:
            print(json.dumps(r))
    sync0 = next(r for r in out["modes"]["sync"] if r["rank"] == 0)
    async0 = next(r for r in out["modes"]["async"] if r["rank"] == 0)
    out["fast_worker_speedup_async_over_sync"] = round(
        async0["steps_per_sec"] / sync0["steps_per_sec"], 3)
    print(json.dumps({
        "metric": "async_fast_worker_speedup_vs_sync",
        "value": out["fast_worker_speedup_async_over_sync"],
        "unit": "x", "straggle_s": args.straggle}))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
        print(json.dumps({"artifact": args.out}))


def main() -> None:
    if os.environ.get("BENCH_PS_ASYNC_WORKER"):
        sys.exit(_async_worker_main())
    p = argparse.ArgumentParser()
    p.add_argument("--model", choices=["resnet50", "gpt2"],
                   default="resnet50")
    p.add_argument("--batch", type=int, default=0,
                   help="default: 64 (resnet50) / 8 (gpt2)")
    p.add_argument("--seq-len", type=int, default=512)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--repeats", type=int, default=3,
                   help="back-to-back measurement rounds; ratios use the "
                        "median across rounds")
    p.add_argument("--num-servers", type=int, default=1,
                   help="CPU server processes (this VM has 1 core; >1 adds "
                        "contention, not parallelism)")
    p.add_argument("--smoke", action="store_true",
                   help="tiny model + CPU-friendly shapes, quick pass")
    p.add_argument("--skip", default="",
                   help="comma-separated paths to skip (e.g. ps,overlap)")
    p.add_argument("--out", default="", help="write JSON artifact here")
    p.add_argument("--trace", default="",
                   help="write a BYTEPS_TRACE_ON timeline JSON here")
    p.add_argument("--async-bench", action="store_true",
                   help="async-vs-sync straggler comparison on a CPU "
                        "fleet (2 workers, worker 1 slowed by --straggle)")
    p.add_argument("--straggle", type=float, default=0.15,
                   help="seconds worker 1 sleeps before each step in "
                        "--async-bench")
    p.add_argument("--async-steps", type=int, default=60,
                   help="timed steps per worker in --async-bench")
    args = p.parse_args()
    if args.async_bench:
        return run_async_bench(args)
    batch = args.batch or {"resnet50": 64, "gpt2": 8}[args.model]
    if args.smoke:
        batch = min(batch, 8)
        args.steps = min(args.steps, 3)

    if (os.environ.get("JAX_PLATFORMS", "").lower() == "cpu"
            and "host_platform_device_count" not in
            os.environ.get("XLA_FLAGS", "")):
        # One CPU device == one async-work thread in the XLA:CPU client;
        # the overlap taps' io_callbacks then deadlock under load (see
        # make_overlapped_train_step's warning). Must be set before jax
        # imports anywhere below.
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=8")

    import jax
    import numpy as np
    import optax

    from bench import device_stamp, require_tpu
    from byteps_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    stamp = device_stamp() if args.smoke else require_tpu("bench_ps.py")
    # --smoke results say so in their names: never a device metric's name.
    tag = "smoke_" if args.smoke else ""

    fleet = provision_fleet(args.num_servers, bool(args.trace))
    results = []
    try:
        import byteps_tpu.jax as bps
        from byteps_tpu.jax.overlap import make_overlapped_train_step
        from byteps_tpu.jax.training import make_train_step

        loss_fn, params, data, items, grad_bytes = build_model(
            args.model, batch, args.seq_len, args.smoke)
        tx = optax.sgd(0.1, momentum=0.9)

        d2h, h2d, probed = host_boundary_microbench(grad_bytes)
        results.append({"metric": f"host_{tag}d2h_gbps",
                        "value": round(d2h, 3),
                        "unit": "GB/s", "bytes": probed, **stamp})
        results.append({"metric": f"host_{tag}h2d_gbps",
                        "value": round(h2d, 3),
                        "unit": "GB/s", "bytes": probed, **stamp})
        print(json.dumps(results[-2]))
        print(json.dumps(results[-1]))

        bps.init()
        host_params = jax.tree_util.tree_map(np.asarray, params)

        def fresh_state():
            ps = jax.tree_util.tree_map(jax.numpy.array, host_params)
            return (ps, tx.init(ps))

        # plain fused step: the no-framework baseline
        @jax.jit
        def plain_step(p_, opt_state, b):
            loss, g = jax.value_and_grad(loss_fn)(p_, b)
            u, opt_state = tx.update(g, opt_state, p_)
            return optax.apply_updates(p_, u), opt_state, loss

        from byteps_tpu.jax.bucketed import make_bucketed_overlap_step
        from byteps_tpu.jax.compression import Compression
        all_paths = {
            "plain": lambda: plain_step,
            "ps": lambda: make_train_step(loss_fn, tx, bps.mesh(),
                                          donate=False),
            # bf16 wire cast INSIDE the grad jit: halves the bytes crossing
            # the host boundary in both directions (D2H of grads, H2D of
            # aggregates).
            "ps_bf16": lambda: make_train_step(
                loss_fn, tx, bps.mesh(), donate=False,
                compression=Compression.bf16, ps_prefix="gradbf16"),
            "overlap": lambda: make_overlapped_train_step(
                loss_fn, tx, prefix="of32"),
            "overlap_bf16": lambda: make_overlapped_train_step(
                loss_fn, tx, wire_dtype="bfloat16", prefix="obf16"),
            # Bucketed overlap (SURVEY §7 hard part #1, io_callback-free).
            # single = one grad program + D2H/DCN/H2D bucket pipeline;
            # multi = one program per bucket, so pushes overlap backward
            # compute too.
            "bucketed_single": lambda: make_bucketed_overlap_step(
                loss_fn, tx, multi_program=False, donate=False,
                prefix="bks"),
            "bucketed_multi": lambda: make_bucketed_overlap_step(
                loss_fn, tx, multi_program=True, donate=False,
                prefix="bkm"),
            "bucketed_bf16": lambda: make_bucketed_overlap_step(
                loss_fn, tx, multi_program=False, donate=False,
                wire_dtype="bfloat16", prefix="bkb"),
        }
        skip = set(s for s in args.skip.split(",") if s)
        unknown = skip - set(all_paths)
        if unknown:
            raise SystemExit(f"--skip: unknown path(s) {sorted(unknown)}; "
                             f"choose from {sorted(all_paths)}")
        if "plain" in skip:
            raise SystemExit("--skip plain: the plain step is the ratio "
                             "baseline and cannot be skipped")
        paths = {n: f for n, f in all_paths.items() if n not in skip}

        # Back-to-back rounds: each round times every path once, so chip /
        # host drift lands inside a round and the per-round ratios cancel
        # it (bench.py's pair-median methodology, generalised).
        times = {name: [] for name in paths}
        built = {name: make() for name, make in paths.items()}
        for _ in range(args.repeats):
            for name, step in built.items():
                times[name].append(
                    _time_steps(step, fresh_state(), data, args.steps))
        for name in paths:
            med = statistics.median(times[name])
            ratios = [tp / t for tp, t in zip(times["plain"], times[name])]
            rec = {
                "metric": f"{args.model}_{tag}{name}_items_per_sec",
                "value": round(items / med, 2),
                "unit": ("images/sec" if args.model == "resnet50"
                         else "sequences/sec"),
                "step_ms": round(med * 1e3, 1),
                "vs_plain": round(statistics.median(ratios), 4),
                **stamp,
                "batch": batch,
                "grad_mbytes": round(grad_bytes / 1e6, 1),
            }
            # The overlap claim, directly: per-round ratio of the
            # like-wire NON-overlapped PS step time to this path's step
            # time (>1.0 = overlap beat tree-serial phases). bf16-wire
            # paths compare against ps_bf16, f32 paths against ps.
            base = "ps_bf16" if name.endswith("bf16") else "ps"
            if name not in (base, "plain") and base in times:
                rec[f"vs_{base}"] = round(statistics.median(
                    [tb / t for tb, t in zip(times[base], times[name])]), 4)
            results.append(rec)
            print(json.dumps(rec))

        trace_path = built.get("overlap") or built.get("ps")
        if args.trace and trace_path is not None:
            # Dedicated trace pass: the Timeline helper merges jax.profiler
            # device spans with the C core's push/pull spans over the
            # BYTEPS_TRACE_START/END_STEP window (docs/timeline.md).
            from byteps_tpu.utils import Timeline
            from byteps_tpu.config import get_config
            cfg = get_config(reload=True)
            tl = Timeline()
            out = trace_path(*fresh_state(), data)
            tl.step()
            for _ in range(cfg.trace_end_step):
                out = trace_path(*out[:-1], data)
                tl.step()
            tl.close()
            combined = os.path.join(cfg.trace_dir, "combined_rank0.json")
            if os.path.exists(combined) and combined != args.trace:
                os.replace(combined, args.trace)
            print(json.dumps({"trace": args.trace}))

        bps.shutdown()
        for pr in fleet:
            pr.wait(timeout=30)
    finally:
        for pr in fleet:
            if pr.poll() is None:
                pr.kill()

    if args.out:
        with open(args.out, "w") as f:
            json.dump({"model": args.model, "batch": batch,
                       "steps": args.steps, "repeats": args.repeats,
                       "num_servers": args.num_servers,
                       **stamp,
                       "results": results}, f, indent=1)
        print(json.dumps({"artifact": args.out}))


if __name__ == "__main__":
    main()

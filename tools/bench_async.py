"""Async vs sync PS training at model scale, with an injected straggler.

VERDICT r4 #6: async mode was only ever measured on a toy MLP with a
synthetic barrier (the 149x "speedup" was just "no barrier"). This bench
runs the real thing: a TransformerLM 6x512 (~20M params, the repo's
mid-size convergence model) trained data-parallel by a 2-worker PS
fleet, sync (make_train_step) vs async (make_async_train_step,
server-resident parameters, FLAG_ASYNC pushes), with worker 1 slowed by
``--straggle-ms`` per step. Both modes run the same WALL-CLOCK budget,
so the artifact answers the question async exists for: how much loss
progress does the fast worker retain per unit time when a straggler
drags the fleet?

Per (mode): each worker reports steps completed, steps/s, and a
loss-vs-wall-clock curve; the driver adds the fast-worker speedup and
the end-of-budget loss comparison. If the C core surfaces the async
staleness counter (server-side push counts carried on acks/pull
responses), per-step staleness stats are included.

Run: PYTHONPATH=. python tools/bench_async.py --out BENCH_async_r05.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.shaped_fleet import cpu_busy_since, run_fleet  # noqa: E402


def worker_main(args) -> None:
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=1")
    import jax  # the fleet is pinned to the CPU by JAX_PLATFORMS alone
    import jax.numpy as jnp
    import numpy as np
    import optax

    import byteps_tpu.jax as bps
    from byteps_tpu.models import TransformerLM, lm_loss

    bps.init()
    client = bps._st().ps_client
    rank = client.worker_rank()
    model = TransformerLM(vocab_size=2048, num_layers=6, d_model=512,
                          num_heads=8, mlp_dim=2048, max_len=512,
                          dtype=jnp.float32)
    # Fixed per-worker corpus (cycled): a learnable task whose loss curve
    # is comparable across modes at equal wall-clock.
    rng = np.random.default_rng(100 + rank)
    corpus = [jnp.asarray(rng.integers(0, 2048, size=(args.batch, args.seq)),
                          jnp.int32) for _ in range(4)]

    def loss_fn(p, batch):
        return lm_loss(model.apply(p, batch), batch)

    tx = optax.sgd(args.lr)
    params = model.init(jax.random.PRNGKey(0), corpus[0])

    if args.mode == "async":
        from byteps_tpu.jax.training import make_async_train_step
        params, step = make_async_train_step(loss_fn, tx, params)
    else:
        from byteps_tpu.jax.training import make_train_step
        params = bps.broadcast_parameters(params)
        step = make_train_step(loss_fn, tx)
    opt_state = tx.init(params)

    # Warm (compile + fleet): excluded from the budget.
    params, opt_state, loss = step(params, opt_state, corpus[0])
    jax.block_until_ready(loss)
    client.barrier()

    curve = []
    steps = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < args.seconds:
        b = corpus[steps % len(corpus)]
        params, opt_state, loss = step(params, opt_state, b)
        loss = float(loss)
        steps += 1
        if args.straggle_ms > 0 and rank == 1:
            time.sleep(args.straggle_ms / 1e3)
        if steps % args.log_every == 0:
            curve.append([round(time.perf_counter() - t0, 2),
                          round(loss, 4)])
    dt = time.perf_counter() - t0
    rec = {
        "rank": rank, "mode": args.mode, "steps": steps,
        "steps_per_s": round(steps / dt, 3),
        "final_loss": round(loss, 4),
        "loss_curve": curve,
    }
    # Staleness stats, if the core surfaces them (round-5 counter).
    if hasattr(client, "async_staleness"):
        rec["staleness"] = client.async_staleness()
    print(json.dumps(rec), flush=True)
    # Async workers finish at different times; the fleet tears down on
    # last-out. A barrier here would re-impose the sync the mode removes.
    bps.shutdown()


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--lr", type=float, default=5e-3)
    p.add_argument("--seconds", type=float, default=120.0)
    p.add_argument("--straggle-ms", type=float, default=1000.0)
    p.add_argument("--log-every", type=int, default=5)
    p.add_argument("--out", default="")
    p.add_argument("--role", default="")
    p.add_argument("--mode", default="sync")
    args = p.parse_args()
    if args.role == "worker":
        return worker_main(args)

    out = {
        "what": ("async vs sync PS training at model scale (TransformerLM "
                 "6x512, 2 workers x 1 server) with worker 1 straggling "
                 f"{args.straggle_ms} ms/step; equal wall-clock budget "
                 f"({args.seconds}s), loss-vs-time curves per worker"),
        "straggle_ms": args.straggle_ms, "seconds": args.seconds,
        "batch": args.batch, "seq": args.seq, "lr": args.lr,
        "modes": {},
    }
    for mode in ("sync", "async"):
        env = {"BYTEPS_PS_MODE": "ps", "JAX_PLATFORMS": "cpu"}
        if mode == "async":
            env["BYTEPS_ENABLE_ASYNC"] = "1"
        _, snap = cpu_busy_since(None)
        rc, recs = run_fleet(
            2, 1,
            [os.path.abspath(__file__), "--role", "worker",
             "--mode", mode, "--batch", str(args.batch),
             "--seq", str(args.seq), "--lr", str(args.lr),
             "--seconds", str(args.seconds),
             "--straggle-ms", str(args.straggle_ms),
             "--log-every", str(args.log_every)],
            env_extra=env, timeout=int(args.seconds) + 600)
        busy, _ = cpu_busy_since(snap)
        if rc != 0 or len(recs) != 2:
            raise SystemExit(f"mode={mode} failed rc={rc}")
        recs.sort(key=lambda r: r["rank"])
        out["modes"][mode] = {"workers": recs, "cpu_busy": busy}
        print(json.dumps([{k: v for k, v in r.items() if k != "loss_curve"}
                          for r in recs]), flush=True)
    sync_fast = out["modes"]["sync"]["workers"][0]
    async_fast = out["modes"]["async"]["workers"][0]
    out["fast_worker_speedup"] = round(
        async_fast["steps_per_s"] / max(sync_fast["steps_per_s"], 1e-9), 2)
    out["final_loss_sync_fast"] = sync_fast["final_loss"]
    out["final_loss_async_fast"] = async_fast["final_loss"]
    print(json.dumps({
        "metric": "async_fast_worker_speedup_model_scale",
        "value": out["fast_worker_speedup"],
        "unit": "x steps/s vs sync under the same straggler",
        "loss_sync": sync_fast["final_loss"],
        "loss_async": async_fast["final_loss"],
    }))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
        print(json.dumps({"artifact": args.out}))


if __name__ == "__main__":
    main()

"""Overlap designs vs link bandwidth, on a kernel-paced DCN.

Measures the four PS step designs on a CPU fleet at realistic,
kernel-enforced link rates (BYTEPS_PACING_RATE — the emulation costs the
host nothing, so compute genuinely overlaps the paced drain):

  serial          make_train_step: jitted grad program, then a blocking
                  host-level ps_push_pull, then apply — the lower bound
                  (step ~= T_compute + T_comm).
  io_callback     make_overlapped_train_step: custom_vjp taps push each
                  layer's gradient DURING backward (CPU backend supports
                  io_callback).
  bucketed_single make_bucketed_overlap_step(multi_program=False): one
                  gradient program; only the D2H/DCN/H2D boundary legs
                  pipeline across buckets.
  bucketed_multi  multi_program=True: one program per bucket, pushes
                  start while later buckets still compute, at a
                  recompute cost XLA prunes per bucket.

Workload: TransformerLM 6x512 (~26M params, the compression bench's
mid model) on the CPU backend, 1 worker x 1 server. A no-comm jitted
step measures T_compute; per (design, rate): step time, plus the
serial-bound (T_compute + T_comm_ideal) and overlap-bound
(max(T_compute, T_comm_ideal)) it sits between, where T_comm_ideal =
2-leg wire bytes / rate.

Run: PYTHONPATH=. python tools/bench_overlap_bw.py --out overlap_bw.json
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
    # io_callback on a SINGLE-device CPU backend can deadlock in XLA's
    # callback machinery under load (overlap.py's own warning); two
    # virtual devices keep the callback executor live. The other designs
    # keep one device so the in-jit collectives stay trivial.
    n_dev = 8 if args.design == "io_callback" else 1
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_dev}")
    import jax  # the fleet is pinned to the CPU by JAX_PLATFORMS alone
    import jax.numpy as jnp
    import numpy as np
    import optax

    import byteps_tpu.jax as bps
    from byteps_tpu.models import TransformerLM, lm_loss

    bps.init()
    model = TransformerLM(vocab_size=2048, num_layers=args.layers,
                          d_model=args.dmodel, num_heads=8,
                          mlp_dim=4 * args.dmodel, max_len=512,
                          dtype=jnp.float32)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(
        rng.integers(0, 2048, size=(args.batch, args.seq)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)

    def loss_fn(p, batch):
        return lm_loss(model.apply(p, batch), batch)

    tx = optax.sgd(1e-3)
    opt_state = tx.init(params)

    design = args.design
    if design == "nocomm":
        @jax.jit
        def step(p, s, b):
            loss, g = jax.value_and_grad(loss_fn)(p, b)
            u, s = tx.update(g, s, p)
            return optax.apply_updates(p, u), s, loss
    elif design == "serial":
        from byteps_tpu.jax.training import make_train_step
        step = make_train_step(loss_fn, tx)
    elif design == "io_callback":
        from byteps_tpu.jax.overlap import make_overlapped_train_step
        step = make_overlapped_train_step(loss_fn, tx)
    elif design == "bucketed_single":
        from byteps_tpu.jax.bucketed import make_bucketed_overlap_step
        step = make_bucketed_overlap_step(loss_fn, tx, n_buckets=4,
                                          multi_program=False)
    elif design == "bucketed_multi":
        from byteps_tpu.jax.bucketed import make_bucketed_overlap_step
        step = make_bucketed_overlap_step(loss_fn, tx, n_buckets=4,
                                          multi_program=True)
    else:
        raise SystemExit(f"unknown design {design!r}")

    for _ in range(args.warmup):
        params, opt_state, loss = step(params, opt_state, tokens)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(args.rounds):
        params, opt_state, loss = step(params, opt_state, tokens)
        jax.block_until_ready(loss)
    dt = (time.perf_counter() - t0) / args.rounds
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(params))
    print(json.dumps({
        "design": design,
        "step_seconds": round(dt, 3),
        "params_m": round(n_params / 1e6, 1),
        "final_loss": round(float(loss), 4),
    }), flush=True)
    bps.shutdown()


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--rates-gbit", default="0.25,1,4")
    p.add_argument("--designs", default="serial,io_callback,"
                                        "bucketed_single,bucketed_multi")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=256)
    p.add_argument("--layers", type=int, default=6)
    p.add_argument("--dmodel", type=int, default=512)
    p.add_argument("--rounds", type=int, default=3)
    p.add_argument("--warmup", type=int, default=2)
    p.add_argument("--partition-mb", type=float, default=1.0)
    p.add_argument("--out", default="")
    p.add_argument("--role", default="")
    p.add_argument("--design", default="serial")
    args = p.parse_args()
    if args.role == "worker":
        return worker_main(args)

    part = int(args.partition_mb * (1 << 20))

    def fleet(design, extra_env):
        env = dict(extra_env, BYTEPS_PARTITION_BYTES=str(part),
                   BYTEPS_PS_MODE="ps", JAX_PLATFORMS="cpu")
        _, snap = cpu_busy_since(None)
        rc, recs = run_fleet(
            1, 1,
            [os.path.abspath(__file__), "--role", "worker",
             "--design", design, "--batch", str(args.batch),
             "--seq", str(args.seq), "--rounds", str(args.rounds),
             "--warmup", str(args.warmup),
             "--layers", str(args.layers), "--dmodel", str(args.dmodel)],
            env_extra=env, timeout=900)
        busy, _ = cpu_busy_since(snap)
        if rc != 0 or not recs:
            raise SystemExit(f"design={design} failed rc={rc}")
        recs[0]["cpu_busy"] = busy
        return recs[0]

    # T_compute: the same jitted step with no PS communication at all.
    base = fleet("nocomm", {})
    t_compute = base["step_seconds"]
    grad_mb = base["params_m"] * 4
    out = {
        "what": ("overlap designs vs kernel-paced link rate, 1 worker x "
                 "1 server, TransformerLM 6x512 f32 on the CPU backend; "
                 "bounds per cell: serial = T_compute + T_comm_ideal, "
                 "overlap = max(T_compute, T_comm_ideal), T_comm_ideal "
                 "= grad bytes / rate per leg (full-duplex legs)"),
        "model_params_m": base["params_m"],
        "grad_mb": round(grad_mb, 1),
        "t_compute_s": t_compute,
        "batch": args.batch, "seq": args.seq,
        "rounds": args.rounds,
        "rates": {},
    }
    print(json.dumps({"t_compute_s": t_compute, "grad_mb": grad_mb}),
          flush=True)
    designs = args.designs.split(",")
    for rate_s in args.rates_gbit.split(","):
        rate = float(rate_s)
        pace = int(rate * 1e9 / 8)
        # BDP-sized credit for the paced link (docs/best-practice.md).
        credit = max(4 * part, int(2.0 * pace))
        env = {"BYTEPS_PACING_RATE": str(pace),
               "BYTEPS_SCHEDULING_CREDIT": str(credit)}
        t_comm = grad_mb * 1e6 / (rate * 1e9 / 8)
        cell = {"t_comm_ideal_s": round(t_comm, 3),
                "bound_serial_s": round(t_compute + t_comm, 3),
                "bound_overlap_s": round(max(t_compute, t_comm), 3),
                "designs": {}}
        for d in designs:
            try:
                r = fleet(d, env)
            except SystemExit as e:  # one design failing must not void
                r = {"error": str(e)}  # the rest of the matrix
            cell["designs"][d] = r
            print(json.dumps({"rate_gbit": rate, "design": d, **r}),
                  flush=True)
        out["rates"][rate_s] = cell
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
        print(json.dumps({"artifact": args.out}))


if __name__ == "__main__":
    main()

"""Compression benchmark driver (BASELINE config 3 at reference scale).

Two modes, both building on example/jax/train_gpt2_compression_byteps.py
(the measurement is always the REAL PS fleet via the launcher — wire
bytes from the van's cumulative counters, both legs):

  --mode converge   CPU fleet, mid-size TransformerLM (6x512, ~29M
                    params): few-hundred-step loss CURVES for dense vs
                    onebit+EF vs topk+EF vs dithering — the "EF closes on
                    dense" claim with its trajectory, not a 25-step
                    endpoint (VERDICT r3 weak #5). topk's wire ratio is
                    re-measured at this size (it is size-dependent).

  --mode chip       the TPU chip as the single worker, GPT2Medium —
                    the reference's 345M configuration by name — with
                    in-jit bf16 wire + onebit+EF on the DCN leg: a few
                    measured steps at the scale BASELINE actually cites.
                    Fails where the worker found no TPU.

This process never imports JAX: every fleet member is a launcher child.
The converge fleet is pinned to the CPU by the JAX_PLATFORMS environment
variable alone; the chip fleet has ONE worker, which holds the chip. The
example places its own compile cache (byteps_tpu.utils.compile_cache).

Writes one JSON artifact (--out) and prints per-run JSON lines.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.abspath(__file__))
EXAMPLE = os.path.join(REPO, "example", "jax",
                       "train_gpt2_compression_byteps.py")


def run_launcher(workers: int, servers: int, example_args, env_extra=None,
                 timeout: float = 3600):
    """One launcher-driven fleet; returns worker 0's parsed JSON line."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(env_extra or {})
    cmd = [sys.executable, "-m", "byteps_tpu.launcher", "--local",
           str(workers), "--num-servers", str(servers), "--",
           sys.executable, EXAMPLE, "--json"] + example_args
    pr = subprocess.run(cmd, env=env, capture_output=True, text=True,
                        timeout=timeout)
    if pr.returncode != 0:
        raise SystemExit(
            f"launcher run failed rc={pr.returncode}:\n{pr.stdout[-3000:]}"
            f"\n{pr.stderr[-2000:]}")
    # Workers write their result line unsynchronised; under the launcher
    # objects can land glued ("{...}{...}") or split across lines, so
    # scan the whole text with raw_decode from every "{" (the
    # tests/test_examples.py recovery shape) and keep result rows only.
    rows = []
    dec = json.JSONDecoder()
    text = pr.stdout
    i = text.find("{")
    while i != -1:
        try:
            obj, end = dec.raw_decode(text[i:])
        except json.JSONDecodeError:
            i = text.find("{", i + 1)
            continue
        if isinstance(obj, dict) and "final_loss" in obj:
            rows.append(obj)
        i = text.find("{", i + end)
    if not rows:
        raise SystemExit(f"no JSON from example:\n{pr.stdout[-2000:]}")
    return rows[0]


def mode_converge(args):
    # (name, compressor config, extra env). wire_quant_int8 (ISSUE 6) is
    # not a per-key codec at all — it arms the block-quantized WIRE
    # (BYTEPS_WIRE_QUANT int8 sub-payloads + worker-side EF residuals +
    # server dequant-sum), so dense vs wire_quant_int8 is the "EF path
    # tracks dense" A/B for the quantized fused wire.
    codecs = [
        ("dense", "", {}),
        ("onebit_ef", "type=onebit;ef=vanilla", {}),
        ("topk_ef", f"type=topk;k={args.topk_k};ef=vanilla", {}),
        ("dithering", "type=dithering;k=4", {}),
        # Round-5 additions (VERDICT r4 weak #7): randomk needs EF to
        # recover the unsampled mass, and the Nesterov momentum decorator
        # had only registry/unit coverage — both now get trajectories.
        ("randomk_ef", f"type=randomk;k={args.topk_k};seed=7;ef=vanilla",
         {}),
        ("topk_nesterov",
         f"type=topk;k={args.topk_k};momentum=nesterov;mu=0.9;ef=vanilla",
         {}),
        ("wire_quant_int8", "", {"BYTEPS_WIRE_QUANT": "1"}),
    ]
    if args.codecs:
        want = set(args.codecs.split(","))
        unknown = want - {n for n, _, _ in codecs}
        if unknown:
            raise SystemExit(f"unknown codecs {sorted(unknown)}")
        codecs = [(n, c, e) for n, c, e in codecs if n in want]
    # ONE virtual device per worker: data parallelism comes from the two
    # worker PROCESSES through the PS fleet (the thing under test); a
    # forced multi-device platform inside each worker adds in-jit
    # collectives whose CPU-backend rendezvous (40 s hard deadline) can
    # wedge under a deep async dispatch queue on a loaded 1-core host —
    # and contributes nothing to a convergence comparison.
    env = {"JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "")
                         + " --xla_force_host_platform_device_count=1")}
    out = {"what": "mid-size convergence curves over a real 2-worker PS "
                   "fleet: dense vs compressed, loss recorded every "
                   f"{args.log_every} steps for {args.steps} steps "
                   "(VERDICT r3: EF claims need trajectories, and topk's "
                   "wire ratio is size-dependent)",
           "model": "TransformerLM 6x512 heads=8 mlp=2048 vocab=2048 "
                    "(~29M params)",
           "steps": args.steps, "batch": args.batch,
           "seq_len": args.seq_len, "runs": []}
    for name, cfg, extra_env in codecs:
        ex_args = ["--model", "mid", "--steps", str(args.steps),
                   "--batch-size", str(args.batch),
                   "--seq-len", str(args.seq_len),
                   "--log-every", str(args.log_every)]
        if cfg:
            ex_args += ["--compressor", cfg]
        row = run_launcher(2, 1, ex_args, env_extra={**env, **extra_env})
        row["codec"] = name
        out["runs"].append(row)
        print(json.dumps({k: v for k, v in row.items()
                          if k != "loss_curve"}))
    dense = next((r for r in out["runs"] if r["codec"] == "dense"), None)
    if dense is not None:
        for r in out["runs"]:
            r["wire_ratio_vs_dense"] = round(
                dense["wire_sent_mb"] / max(r["wire_sent_mb"], 1e-9), 1)
            r["final_loss_gap_vs_dense"] = round(
                r["final_loss"] - dense["final_loss"], 4)
    return out


def mode_chip(args):
    out = {"what": "GPT2Medium (the reference's 345M compression-bench "
                   "model, BASELINE config 3) trained on the TPU chip "
                   "through the full PS path: in-jit bf16 wire for the "
                   "host boundary + C-core codec on the DCN leg",
           "runs": []}
    configs = [
        ("bf16_onebit_ef", ["--wire", "bf16", "--compressor",
                            "type=onebit;ef=vanilla"]),
        ("bf16_dense", ["--wire", "bf16"]),
    ]
    if args.codecs:
        # Same validation as mode_converge: unknown names must error, not
        # silently filter to an empty run list and write a hollow artifact.
        want = set(args.codecs.split(","))
        unknown = want - {n for n, _ in configs}
        if unknown:
            raise SystemExit(
                f"unknown codecs {sorted(unknown)} for --mode chip; "
                f"choose from {sorted(n for n, _ in configs)}")
        configs = [(n, e) for n, e in configs if n in want]
    for name, extra in configs:
        row = run_launcher(
            1, 1, ["--model", "gpt2_medium", "--steps", str(args.steps),
                   "--batch-size", str(args.batch),
                   "--seq-len", str(args.seq_len)] + extra,
            timeout=5400)
        if row["platform"] != "tpu":
            raise SystemExit(
                "--mode chip measures the TPU, but the worker ran on "
                f"{row['platform']!r} ({row['device_kind']}); run it "
                "through the chip tool")
        row["config"] = name
        out["runs"].append(row)
        print(json.dumps(row))
    return out


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--mode", choices=["converge", "chip"],
                   default="converge")
    p.add_argument("--steps", type=int, default=0,
                   help="default: 200 (converge) / 2 (chip)")
    p.add_argument("--batch", type=int, default=0,
                   help="default: 8 (converge) / 4 (chip). Converge "
                        "default is sized for a 1-core CPU fleet "
                        "(~8 s/step at the 29M model): codec behaviour "
                        "(topk ratio, EF residual scale) is driven by "
                        "MODEL size, which stays mid-size")
    p.add_argument("--seq-len", type=int, default=0,
                   help="default: 64 (converge) / 256 (chip)")
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--topk-k", type=int, default=4096)
    p.add_argument("--codecs", default="",
                   help="comma-separated subset of the converge codec "
                        "names (default: all). Lets a round re-measure "
                        "only what it adds and merge artifacts")
    p.add_argument("--out", default="")
    args = p.parse_args()
    dflt = {"converge": (200, 8, 64), "chip": (2, 4, 256)}[args.mode]
    args.steps = args.steps or dflt[0]
    args.batch = args.batch or dflt[1]
    args.seq_len = args.seq_len or dflt[2]
    out = (mode_converge if args.mode == "converge" else mode_chip)(args)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
        print(json.dumps({"artifact": args.out}))


if __name__ == "__main__":
    main()

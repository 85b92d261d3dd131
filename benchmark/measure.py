"""Repeat cells as the driver does and say how far the runs spread.

    python benchmark/measure.py --cells a,b [--sets 2] [--runs 6] [--trace 1]

For every cell: optionally one traced run, then ``--sets`` sets of ``--runs``
runs of the command, each a new process, for ``run_seconds`` of
``BENCHMARK.json``; a set's runs have another ``--seed`` each and every set
has the same seeds. Prints, per cell and end-to-end metric, each set's median
and spread (distance between the quartiles, as ``statistics.quantiles(values,
n=4)`` gives them, over the median), the bound the rule gives (five times the
wider spread, never under 1%) and the two figures the driver's check holds a
bound to: ``tight`` (mean over the sets of the spread less the set's run
farthest from its median; a bound under twice that is too tight) and
``loose`` (the wider spread; a bound over eight times that, and over 1%, is
too loose). Every run's result line goes to
``chiprun_out/benchmark/runs-<first seed>.jsonl``. This process never imports JAX: a
chip belongs to one process, and that is the run's.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "chiprun_out", "benchmark")


def run_once(manifest, cell, seed, trace, log):
    argv = [sys.executable, *manifest["command"][1:], "--workload", cell,
            "--seed", str(seed), "--seconds", str(manifest["run_seconds"]),
            "--trace", str(trace)]
    t = time.time()
    proc = subprocess.run(argv, cwd=REPO, capture_output=True, text=True)
    lines = proc.stdout.strip().splitlines()
    record = {"cell": cell, "seed": seed, "trace": trace, "rc": proc.returncode,
              "process_s": round(time.time() - t, 1),
              "result": json.loads(lines[-1]) if proc.returncode == 0 and lines
              else None,
              "diagnostics": proc.stderr.strip().splitlines()[-1:]}
    if record["result"] is None:
        record["stderr_tail"] = proc.stderr[-3000:]
    log.write(json.dumps(record) + "\n")
    log.flush()
    print(json.dumps(record)[:1500], flush=True)
    return record


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def spread_less_farthest(values):
    med = statistics.median(values)
    kept = sorted(values, key=lambda v: abs(v - med))[:-1]
    return spread(kept) if len(kept) >= 2 else 0.0


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--trace", type=int, default=0,
                    help="1: a traced run of each cell before its sets")
    ap.add_argument("--first-seed", type=int, default=100)
    args = ap.parse_args()
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    os.makedirs(OUT, exist_ok=True)
    seed = args.first_seed
    with open(os.path.join(OUT, f"runs-{args.first_seed}.jsonl"), "a") as log:
        for cell in args.cells.split(","):
            if args.trace:
                run_once(manifest, cell, seed, 1, log)
                seed += 1
                # the recorded event list, before the next run clears it
                subprocess.run(
                    [sys.executable, "benchmark/dump_events.py",
                     os.path.join(".benchmark_out", cell, "trace"),
                     os.path.join(OUT, cell + ".events.json.gz")],
                    cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"))
            sets = []
            for _ in range(args.sets):
                sets.append([run_once(manifest, cell, seed + i, 0, log)
                             for i in range(args.runs)])
            seed += args.runs
            for metric in manifest["end_to_end"]:
                name, per_set = metric["name"], []
                for runs in sets:
                    values = [r["result"]["metrics"][name]["value"]
                              for r in runs if r["result"]]
                    if len(values) >= 2:
                        per_set.append({"median": statistics.median(values),
                                        "spread": spread(values),
                                        "less_farthest":
                                        spread_less_farthest(values),
                                        "n": len(values)})
                if per_set:
                    widest = max(s["spread"] for s in per_set)
                    print(json.dumps({
                        "cell": cell, "metric": name, "sets": per_set,
                        "bound_by_rule": max(0.01, 5 * widest),
                        "tight": statistics.mean(
                            s["less_farthest"] for s in per_set),
                        "loose": widest,
                        "bound": metric["bound"]}), flush=True)


if __name__ == "__main__":
    main()

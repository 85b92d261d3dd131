"""Run one cell of the benchmark once.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell asks
for. The last line of standard output is one JSON object with the keys
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and
``breakdown`` with ``--trace 1``): with ``--trace 0`` the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics. Without the device the
cell asks for, the exit code is not 0 and no result is printed: a cell never
falls back to the CPU. There is no option for a size or a platform; see
``benchmark/README.md``.
"""

import time

T0 = time.perf_counter()  # set-up counts from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None, *, steer=None, t0=None) -> int:
    """``steer`` is for ``tests/benchmark`` only (a tiny size on the CPU);
    the command passes none."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from benchmark.lib import cell, device

    manifest = cell.load_json(os.path.join(REPO, "BENCHMARK.json"))
    try:
        result = cell.run_cell(
            REPO, manifest, args.workload, seed=args.seed,
            seconds=args.seconds, trace=bool(args.trace),
            t0=T0 if t0 is None else t0, steer=steer or cell.Steer())
    except device.DeviceError as e:
        print(f"benchmark/run.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

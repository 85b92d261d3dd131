"""The tiny size of the CPU rehearsals, steered from outside the command.

``benchmark/run.py`` has no option for a size, a platform or a trace layout:
it always runs the configuration files' sizes on a TPU. The tests hand
``run.main`` a ``Steer`` instead (on-chip-measurement guide §2: rehearse the
control flow on the CPU at a tiny size); the size is data, the
``rehearsal_sizing`` of the cell's configuration file. As a script,

    python tests/benchmark/bench_tiny.py <workload> <trace>

runs one cell that way in a process of its own; the caller sets
``JAX_PLATFORMS=cpu`` and as many virtual devices as the cell has chips.
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def steer(workload: str):
    """The ``Steer`` of a CPU rehearsal of ``workload``: the tiny size its
    configuration file keeps for it (``rehearsal_sizing``), found through
    the manifest as the command finds the cell's files."""
    from benchmark.lib import cell, trace_reduce

    manifest = cell.load_json(os.path.join(REPO, "BENCHMARK.json"))
    entry = cell.find_config(manifest, cell.find_cell(manifest, workload))
    sizing = cell.load_json(os.path.join(REPO, entry["file"]))[
        "rehearsal_sizing"]
    # The CPU backend's capture has no device plane: its executor's events
    # sit on the host plane's thread lines. Good enough to walk the code.
    cpu = trace_reduce.Layout(device_plane=r"^/host:CPU$", op_lines=None,
                              sync_line=None, module_line="none")
    return cell.Steer(sizing=sizing, platform="cpu", layout=cpu,
                      peaks={"bf16_flops_per_s": 1e12}, compile_cache=False)


if __name__ == "__main__":
    from benchmark import run

    workload, trace = sys.argv[1:3]
    sys.exit(run.main(
        ["--workload", workload, "--seed", "1", "--seconds", "1", "--trace",
         trace], steer=steer(workload)))

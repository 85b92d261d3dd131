"""The tiny size of the CPU rehearsals, steered from outside the command.

``benchmark/run.py`` has no option for a size, a platform or a trace layout:
it always runs the configuration files' sizes on a TPU. The tests hand
``run.main`` a ``Steer`` instead (on-chip-measurement guide §2: rehearse the
control flow on the CPU at a tiny size). As a script,

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

SIZING = {
    "gpt2-124m": dict(n_layer=2, n_embd=64, n_head=4, n_inner=128,
                      vocab_size=512, n_positions=64, seq_len=32,
                      batch_per_chip=2, reference_micro_batch_rows=2),
    "bert-large": dict(num_hidden_layers=2, hidden_size=64,
                       num_attention_heads=4, intermediate_size=128,
                       vocab_size=512, max_position_embeddings=64,
                       seq_len=32, batch_per_chip=4,
                       reference_micro_batch_rows=2),
}


def steer(config_name: str):
    from benchmark.lib import cell, trace_reduce

    # The CPU backend's capture has no device plane: its executor's events
    # sit on the host plane's thread lines. Good enough to walk the code.
    cpu = trace_reduce.Layout(device_plane=r"^/host:CPU$", op_lines=None,
                              sync_line=None, module_line="none")
    return cell.Steer(sizing=SIZING[config_name], platform="cpu", layout=cpu,
                      peaks={"bf16_flops_per_s": 1e12}, compile_cache=False)


if __name__ == "__main__":
    from benchmark import run

    workload, trace = sys.argv[1:3]
    sys.exit(run.main(
        ["--workload", workload, "--seed", "1", "--seconds", "1", "--trace",
         trace], steer=steer(workload.split(".")[0])))

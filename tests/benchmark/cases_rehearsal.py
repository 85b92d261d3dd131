"""The command off the chip: its refusal, and whole cells at a tiny size
through ``benchmark/run.py``'s own code path, on 1 and on 4 virtual devices.
Each rehearsal is a process of its own: a cell requires exactly as many
devices as it has chips, and ``bps.init()`` takes all there are."""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
from bench_tiny import REPO  # noqa: E402

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
END_TO_END = {"tokens_per_s_per_chip", "step_ms_p50", "mfu_pct",
              "peak_hbm_gb", "setup_s"}


def _python(argv, devices, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    return subprocess.run([sys.executable, *argv], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=timeout)


def _rehearse(workload, trace, devices, root=REPO):
    """``bench_tiny.py`` of the benchmark under ``root``, the program from
    this repo."""
    out = _python([os.path.join(root, "tests", "benchmark", "bench_tiny.py"),
                   workload, str(trace)], devices)
    assert out.returncode == 0, out.stdout + out.stderr
    last = json.loads(out.stdout.strip().splitlines()[-1])
    diagnostics = json.loads(out.stderr.strip().splitlines()[-1])
    return last, diagnostics


def test_the_command_refuses_a_cpu():
    """As the driver calls it, where JAX finds no TPU: another exit code
    than 0 and no result, never a device metric from a CPU."""
    out = _python([os.path.join(REPO, "benchmark", "run.py"), "--workload",
                   "gpt2-124m.collective.1chip", "--seed", "0", "--seconds",
                   "1", "--trace", "0"], devices=1)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs 1 tpu device(s)" in out.stderr
    assert "'platform': 'cpu'" in out.stderr


def test_the_command_refuses_an_unknown_workload():
    out = _python([os.path.join(REPO, "benchmark", "run.py"), "--workload",
                   "nope", "--seed", "0", "--seconds", "1", "--trace", "0"],
                  devices=1)
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "no workload 'nope'" in out.stderr


def test_one_chip_cell_end_to_end_line():
    last, diag = _rehearse("gpt2-124m.collective.1chip", 0, devices=1)
    assert set(last) == RESULT_KEYS
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] == diag["steps"] > 0
    assert set(last["metrics"]) == END_TO_END
    for name, m in last["metrics"].items():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)
    assert last["metrics"]["step_ms_p50"]["unit"] == "ms"
    assert last["device"] == {"platform": "cpu", "kind": "cpu", "count": 1,
                              "memory_peak_bytes": 0}
    assert diag["problems"] == [] and diag["agreement"]["ok"]
    assert diag["agreement"]["max_loss_diff"] < 2e-3


def test_four_chip_cell_traced_line():
    last, diag = _rehearse("bert-large.collective.4chip", 1, devices=4)
    assert set(last) == RESULT_KEYS | {"breakdown"}
    assert last["correct"] is True and last["failed"] == 0
    assert set(last["metrics"]) == {
        "step.device_ms", "step.programs_per_step", "ici.collective_ms",
        "ici.exposed_ms", "device.idle_pct", "setup.compile_s"}
    device = last["device"]
    assert device["count"] == 4 and 0 < device["busy_s"] <= device["window_s"]
    assert set(last["breakdown"]) == {"device_ops", "idle_gaps"}
    for rows in last["breakdown"].values():
        assert 0 < len(rows) <= 10
        assert all(isinstance(n, str) and s >= 0 for n, s in rows)
    assert diag["traced_steps"] == 10 and diag["problems"] == []


def test_a_share_cell_s_traced_line_has_its_window():
    """A share cell tiny on the CPU: the traced run goes on to the window's
    end, so the drift of the whole series and the probe at the window's end
    are on the line beside the probe at its start, and the diagnostics
    line says how far round its pool of 128 the run went."""
    last, diag = _rehearse("laguna-xs.2.collective-swa.1chip", 1, devices=1)
    assert last["correct"] is True and diag["problems"] == []
    m = last["metrics"]
    assert 0.5 < m["share.held_load_end"]["value"] < 2.0
    assert m["share.held_load_end"]["value"] == \
        diag["probes"]["share_held_load_end"]
    assert len(diag["probes"]["share_held_load_end_by_layer"]) == 3
    assert "eshare.held_load" in m
    assert diag["probes"]["eshare_expert_layers"] == 3
    assert len(diag["step_ms_series"]) == diag["step_samples"] >= 6
    assert m["share.step_drift_pct"]["unit"] == "%"
    assert diag["pool_cycles"] == (3 + last["attempted"]) / 128
    assert diag["traced_steps"] == 8 < diag["steps"]


def test_a_cell_needs_exactly_its_chips():
    out = _python([os.path.join(HERE, "bench_tiny.py"),
                   "bert-large.collective.4chip", "0"], devices=2)
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "needs 4 cpu device(s)" in out.stderr


def test_an_added_cell_runs_from_a_copy_of_the_benchmark(tmp_path):
    """What ``additions.py`` adds — a cut configuration with another batch,
    a traffic file, a reader, a cell — laid over a copy of the benchmark and
    run from there, tiny, through the copy's own ``run.py``: no file that
    was there had to change, and the new reader's metric is on the line."""
    import additions

    manifest, files = additions.additions(
        json.load(open(os.path.join(REPO, "BENCHMARK.json"))), REPO)
    additions.copy_benchmark(REPO, tmp_path)
    additions.write(tmp_path, manifest, files)
    last, diag = _rehearse(additions.CELLS[1], 1, devices=1, root=tmp_path)
    assert last["correct"] is True and last["failed"] == 0
    assert set(last["metrics"]) == {
        "step.device_ms", "step.programs_per_step", "device.idle_pct",
        "setup.compile_s", "counted.steps_per_fetch"}
    assert 0 < last["metrics"]["counted.steps_per_fetch"]["value"] <= 5
    # six layers cut to the rehearsal's two, by the new file's own sizing
    assert diag["problems"] == [] and diag["n_params"] == 103_936
    # the new reader's ``finish``: once, after the window, its last state
    assert diag["probes"]["counted_finish_calls"] == 1
    assert diag["probes"]["counted_finish_saw_steps"] == diag["steps"] > 0
    assert diag["probes"]["counted_finish_leaves"] > 0
    # steps started, warm-up and window, over the pool of 16
    assert diag["pool_cycles"] == (3 + last["attempted"]) / 16
    assert os.path.isdir(tmp_path / ".benchmark_out" / additions.CELLS[1])


def test_the_command_fails_where_only_the_benchmark_is(tmp_path):
    """In a directory that holds BENCHMARK.json and the files under `paths`
    and nothing of the program: another exit code than 0, no result."""
    import additions

    additions.copy_benchmark(REPO, tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "gpt2-124m.collective.1chip", "--seed", "0", "--seconds", "1",
         "--trace", "0"], env=env, cwd=tmp_path, capture_output=True,
        text=True, timeout=300)
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "No module named 'byteps_tpu'" in out.stderr

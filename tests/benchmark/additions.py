"""What a later PR may do to the benchmark, done by the tests themselves.

A PR outside the benchmark may add files under ``benchmark/`` and entries to
``BENCHMARK.json`` and edit nothing that is there. ``additions`` makes such
a PR in memory — new files and appended entries only — and ``write`` lays it
over a copy of the benchmark (``copy_benchmark``): a configuration cut in
depth with another batch, a traffic file that names a new reader, the reader,
two cells, and a per-layer metric of the reader listed for one of them alone.
``test_benchmark_manifest.py`` holds the copy to every check it holds the
real tree to; ``cases_rehearsal.py`` runs the new cell tiny through
``run.py`` from the copy. Whatever these need changed in a file that is
there is a wall for the next configuration.
"""

import copy
import json
import os
import shutil

CONFIG, TRAFFIC, READER = "gpt2-124m-6l", "counted.1chip", "counted"
CELLS = (f"{CONFIG}.collective.1chip", f"{CONFIG}.{TRAFFIC}")

READER_SOURCE = '''"""Layer: loop (``benchmark/lib/loop.py``).

Steps completed per loss fetch over the window: a count. ``SPANS`` names the
host event of the fetch itself, so that the device's idle time under it reads
by that name."""

LAYER = "loop"
SPANS = ("np.asarray(jax.Array)",)
METRICS = {
    "counted.steps_per_fetch": {"unit": "count", "better": "higher",
                                "source": "program_counter",
                                "moves": "tokens_per_s_per_chip"},
}


def finish(run, state):
    """``lib/cell.py`` calls this once, after the window and with its last
    state: what it saw goes to the diagnostics line's ``probes``."""
    import jax

    run.probes["counted_finish_calls"] = (
        run.probes.get("counted_finish_calls", 0) + 1)
    run.probes["counted_finish_saw_steps"] = run.window.completed
    run.probes["counted_finish_leaves"] = len(
        jax.tree_util.tree_leaves(state[0]))


def read(run):
    if not run.window.step_s:
        return {}
    return {"counted.steps_per_fetch":
            run.window.completed / len(run.window.step_s)}
'''


def additions(manifest: dict, root: str):
    """``(manifest with the additions appended, {relative path: text})``,
    built from the GPT-2 configuration under ``root``."""
    configs = os.path.join(root, "benchmark", "configs")
    with open(os.path.join(configs, "gpt2-124m.json")) as f:
        body = json.load(f)
    d = body["n_embd"]
    body.update(
        name=CONFIG, n_layer=6, reduced=["n_layer 12 -> 6"],
        batch_per_chip=4, tokens_per_step_per_chip=4 * body["seq_len"],
        reference_micro_batch_rows=4,
        # by hand: a layer is 12 d^2 weights + 13 d biases and LayerNorms
        n_params=body["n_params"] - 6 * (12 * d * d + 13 * d),
        deployment="data parallel over whole replicas of the cut model: "
                   "every chip holds all six layers and a batch of 4 x 1024")
    del body["compiled_bytes"]
    with open(os.path.join(configs, "gpt2-124m.py")) as f:
        config_py = f.read()
    with open(os.path.join(root, "benchmark", "traffic",
                           "collective.1chip.json")) as f:
        traffic = json.load(f)
    traffic.update(name=TRAFFIC, log_every=5,
                   readers=traffic["readers"] + [READER])
    files = {
        f"benchmark/configs/{CONFIG}.json": json.dumps(body, indent=2),
        f"benchmark/configs/{CONFIG}.py": config_py,
        f"benchmark/traffic/{TRAFFIC}.json": json.dumps(traffic, indent=2),
        f"benchmark/layers/{READER}.py": READER_SOURCE,
    }
    out = copy.deepcopy(manifest)
    source = next(c for c in manifest["configs"] if c["name"] == "gpt2-124m")
    out["configs"].append({
        "name": CONFIG, "source": source["source"],
        "file": f"benchmark/configs/{CONFIG}.json", "reduced": ["n_layer"],
        "why": "GPT-2 small cut to six layers, b4 x s1024: what a cut "
               "configuration with another batch asks of the harness"})
    for name, traffic_name, why in (
            (CELLS[0], "collective.1chip", "a new configuration on a job "
             "that is there: b4 x s1024, collective mode, no fleet"),
            (CELLS[1], TRAFFIC, "the same with a loss fetch every 5 steps "
             "and a reader of its own")):
        out["workloads"].append({"name": name, "config": CONFIG,
                                 "traffic": traffic_name, "chips": 1,
                                 "why": why})
    out["per_layer"].append({
        "name": "counted.steps_per_fetch", "unit": "count",
        "better": "higher", "source": "program_counter", "layer": "loop",
        "moves": "tokens_per_s_per_chip", "workloads": [CELLS[1]]})
    return out, files


def copy_benchmark(repo: str, dst: str) -> None:
    """``BENCHMARK.json`` and the files under its ``paths``, nothing else."""
    shutil.copy(os.path.join(repo, "BENCHMARK.json"), dst)
    with open(os.path.join(repo, "BENCHMARK.json")) as f:
        paths = json.load(f)["paths"]
    for path in paths:
        shutil.copytree(os.path.join(repo, path), os.path.join(dst, path),
                        ignore=shutil.ignore_patterns("__pycache__"))


def write(root: str, manifest: dict, files: dict) -> None:
    """Lay the additions over the benchmark under ``root``. A file that is
    there is never written over: an addition is a new file."""
    for rel, text in files.items():
        with open(os.path.join(root, rel), "x") as f:
            f.write(text)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f, indent=1)

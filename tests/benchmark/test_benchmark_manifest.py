"""BENCHMARK.json against the files it names, and against its own rules.

Every check is a function of a manifest and the root its files lie under,
and runs twice: over the real tree, and over a copy to which ``additions.py``
has added — as new files and appended entries only — a cut configuration
with another batch, a traffic file, a reader, two cells and a per-layer
metric. What holds for the copy is what a later PR may count on."""

import os
import re
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import additions  # noqa: E402
from bench_tiny import REPO  # noqa: E402

from benchmark.lib import cell as cell_lib  # noqa: E402

MANIFEST = cell_lib.load_json(os.path.join(REPO, "BENCHMARK.json"))
ADDED, ADDED_FILES = additions.additions(MANIFEST, REPO)
TREES = {"real": MANIFEST, "added": ADDED}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
REDUCED = re.compile(r"^(\S+) (\S+) -> (\S+)$")
LISTS = ("configs", "workloads", "end_to_end", "per_layer")


@pytest.fixture(scope="module")
def roots(tmp_path_factory):
    """Where each tree's files lie: the repo, and the copy with the
    additions laid over it."""
    copy = tmp_path_factory.mktemp("benchmark_copy")
    additions.copy_benchmark(REPO, copy)
    additions.write(copy, ADDED, ADDED_FILES)
    return {"real": REPO, "added": str(copy)}


def over(kind):
    """One case per entry of ``kind`` in each tree."""
    return pytest.mark.parametrize("tree,entry", [
        pytest.param(tree, e, id=f"{tree}-{e['name']}")
        for tree, manifest in TREES.items() for e in manifest[kind]])


each_tree = pytest.mark.parametrize("tree", list(TREES))


def _traffic(root, cell):
    return cell_lib.load_json(os.path.join(
        root, "benchmark", "traffic", cell["traffic"] + ".json"))


def _reader(root, name):
    return cell_lib.load_module(
        os.path.join(root, "benchmark", "layers", name + ".py"),
        f"layer_{name}")


def test_the_additions_are_additions(roots):
    """New files, and entries appended to the manifest's lists: nothing
    that is there is edited, which is all a PR outside the benchmark may
    do."""
    for rel in ADDED_FILES:
        assert not os.path.exists(os.path.join(REPO, rel)), rel
        assert os.path.exists(os.path.join(roots["added"], rel)), rel
    for key, value in MANIFEST.items():
        if key in LISTS:
            assert ADDED[key][:len(value)] == value and \
                len(ADDED[key]) >= len(value)
        else:
            assert ADDED[key] == value
    # a cut, and another batch than the configuration it was made from
    config = ADDED["configs"][-1]
    body = cell_lib.load_json(os.path.join(roots["added"], config["file"]))
    made_from = cell_lib.load_json(os.path.join(
        REPO, "benchmark", "configs", "gpt2-124m.json"))
    assert config["reduced"] and not made_from["reduced"]
    assert (body["tokens_per_step_per_chip"]
            != made_from["tokens_per_step_per_chip"])
    assert len(ADDED["workloads"]) == len(MANIFEST["workloads"]) + 2
    metric = ADDED["per_layer"][-1]
    assert metric["workloads"] == [ADDED["workloads"][-1]["name"]]
    assert metric["name"].split(".")[0] in _traffic(
        roots["added"], ADDED["workloads"][-1])["readers"]


@each_tree
def test_top_level_keys_and_limits(roots, tree):
    manifest, cells = TREES[tree], TREES[tree]["workloads"]
    assert set(manifest) == {"command", "paths", "run_seconds", *LISTS}
    assert manifest["paths"] == ["benchmark", "tests/benchmark"]
    assert manifest["command"] == ["python3", "benchmark/run.py"]
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(
        os.path.join(roots[tree], "BENCHMARK.json")) < 64 * 1024
    assert 1 <= len(cells) <= 24 and 1 <= len(manifest["configs"]) <= 24
    assert 1 <= len(manifest["per_layer"]) <= 128
    assert 1 <= len(manifest["end_to_end"]) <= 16
    four = [c for c in cells if c["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)


@over("configs")
def test_configuration_files(roots, tree, entry):
    config, root = entry, roots[tree]
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"]) and len(config["why"]) <= 200
    assert config["source"].startswith("https://")
    assert config["file"] == f"benchmark/configs/{config['name']}.json"
    body = cell_lib.load_json(os.path.join(root, config["file"]))
    assert body["source"] == config["source"]
    # The file says each cut as "<key> <published> -> <here>", <key> a key
    # of the file whose value is <here>; the manifest lists the keys (a name
    # there has no space). A cut model says what deployment it stands for.
    cuts = [REDUCED.match(r).groups() for r in body["reduced"]]
    assert config["reduced"] == [key for key, _, _ in cuts]
    assert len(cuts) <= 16 and all(NAME.match(key) for key, _, _ in cuts)
    for key, published, here in cuts:
        assert str(body[key]) == here != published
    assert body["deployment"] if cuts else "deployment" in body
    assert set(body["rehearsal_sizing"]) <= set(body)
    module = cell_lib.load_module(
        os.path.join(root, config["file"][:-5] + ".py"), "cfg")
    for fn in ("build", "make_batch", "reference_weights", "reference_loss",
               "flops_per_token"):
        assert callable(getattr(module, fn)), fn
    assert any(c["config"] == config["name"]
               for c in TREES[tree]["workloads"])
    assert (body["tokens_per_step_per_chip"]
            == body["batch_per_chip"] * body["seq_len"])


@over("workloads")
def test_cell_files_found_by_name(roots, tree, entry):
    cell, manifest, root = entry, TREES[tree], roots[tree]
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    assert cell["config"] in {c["name"] for c in manifest["configs"]}
    traffic = _traffic(root, cell)
    assert traffic["chips"] == cell["chips"] and cell["chips"] in (1, 4)
    assert traffic["mode"] in ("collective", "ps")
    assert callable(cell_lib.resolve(traffic["step_builder"]))
    assert traffic["guarantees"]
    # the cell reports setup_s, another end-to-end metric and, through its
    # readers, at least one per-layer metric the manifest lists for it
    assert len(cell_lib.metrics_for(manifest, "end_to_end",
                                    cell["name"])) >= 2
    listed = set(cell_lib.metrics_for(manifest, "per_layer", cell["name"]))
    readers = [_reader(root, r) for r in traffic["readers"]]
    declared = set().union(*(r.METRICS for r in readers))
    assert listed and listed <= declared, listed - declared
    # the spans its readers name go to the reduction as names
    assert all(isinstance(s, str) and s
               for s in cell_lib.reader_spans(readers))


def test_the_loader_finds_a_cell_s_files_under_the_root_it_is_given(roots):
    """``run.py`` hands ``load_cell`` the root of its own checkout: the
    added cell's configuration, traffic file and readers are found in the
    copy, and not in the repo, which has none of them."""
    name = additions.CELLS[1]
    run = cell_lib.load_cell(roots["added"], ADDED, name, cell_lib.Steer())
    assert run.traffic["name"] == additions.TRAFFIC and run.chips == 1
    assert run.cfg["n_layer"] == 6 and run.rows == 4
    assert [r.LAYER for r in run.readers][-1] == "loop"
    assert run.out_dir == os.path.join(roots["added"], ".benchmark_out", name)
    with pytest.raises(FileNotFoundError):
        cell_lib.load_cell(REPO, ADDED, name, cell_lib.Steer())
    assert not os.path.exists(os.path.join(REPO, ".benchmark_out", name))


@each_tree
def test_cells_and_pairs_are_unique(tree):
    cells = TREES[tree]["workloads"]
    metrics = TREES[tree]["end_to_end"] + TREES[tree]["per_layer"]
    assert len({c["name"] for c in cells}) == len(cells)
    assert len({(c["config"], c["traffic"]) for c in cells}) == len(cells)
    assert len({m["name"] for m in metrics}) == len(metrics)
    configs = TREES[tree]["configs"]
    assert len({c["name"] for c in configs}) == len(configs)
    assert len({c["file"] for c in configs}) == len(configs)


@over("end_to_end")
def test_end_to_end_metric(tree, entry):
    metric = entry
    assert set(metric) <= {"name", "unit", "better", "bound", "source",
                           "workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.1


@each_tree
def test_the_five_end_to_end_metrics(tree):
    assert [m["name"] for m in TREES[tree]["end_to_end"]] == [
        "tokens_per_s_per_chip", "step_ms_p50", "mfu_pct", "peak_hbm_gb",
        "setup_s"]


@over("per_layer")
def test_per_layer_metric_has_a_reader_that_declares_it(roots, tree, entry):
    metric, manifest = entry, TREES[tree]
    assert set(metric) <= {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["moves"] in {m["name"] for m in manifest["end_to_end"]}
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    cells = {c["name"]: c for c in manifest["workloads"]}
    for name in metric.get("workloads", []):
        assert name in cells
    reader_name = metric["name"].split(".")[0]
    reader = _reader(roots[tree], reader_name)
    declared = reader.METRICS[metric["name"]]
    assert reader.LAYER == metric["layer"]
    assert {k: metric[k] for k in declared} == declared
    # a cell the metric is listed for (every cell, without the key) has the
    # reader in its traffic file: a traced line without it is refused
    for name in metric.get("workloads", cells):
        assert reader_name in _traffic(roots[tree], cells[name])["readers"], \
            name


SHARE_CELLS = next(m["workloads"] for m in MANIFEST["per_layer"]
                   if m["name"] == "share.step_drift_pct")
# its pass bound is every assignment, so its step does not turn on the routing
STEP_FREE_OF_THE_ROUTING = "zaya1-8b.collective-cca.1chip"


@pytest.mark.parametrize("name", SHARE_CELLS)
def test_no_share_cell_s_window_sees_a_batch_twice(name):
    """A share's step turns on its routing and the routing on what the
    model has seen: a window over a cycled pool swings with the seed (PERF.md
    section 6, PRs 63 and 66). Every cell whose drift is on the ledger has
    the reader and, but for the one whose step does not turn on the routing,
    draws a pool no 30 s window cycles (``pool_cycles`` on the diagnostics
    line of a run says whether one did)."""
    cell = cell_lib.find_cell(MANIFEST, name)
    traffic = _traffic(REPO, cell)
    assert "share" in traffic["readers"]
    assert traffic["log_every"] == 4 and traffic["trace_steps"] == 8
    if name != STEP_FREE_OF_THE_ROUTING:
        assert traffic["batch_pool"] >= 64


@each_tree
def test_paths_hold_only_well_named_files(roots, tree):
    allowed = re.compile(r"^[A-Za-z0-9_./-]+$")
    for path in TREES[tree]["paths"]:
        for root, dirs, files in os.walk(os.path.join(roots[tree], path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(root, f), roots[tree])
                assert allowed.match(rel), rel


def test_peaks_are_keyed_by_device_kind_and_unknown_is_an_error():
    from benchmark.lib import device

    assert device.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    for kind in ("cpu", "TPU v4", "_source"):
        with pytest.raises(device.DeviceError, match="no published peaks"):
            device.peaks(kind)


@each_tree
def test_result_line_keys_are_the_contract_s(tree):
    """What run.py prints is json.dumps of run_cell's dict: its keys are
    checked in the rehearsals; here, that metrics_for follows `workloads`:
    a metric without the key belongs to every cell, one with it to the
    cells it lists, and no two kinds of cell get the same set by chance."""
    made_up = {"per_layer": [{"name": "all.x"},
                             {"name": "some.y", "workloads": ["a", "b"]}]}
    assert set(cell_lib.metrics_for(made_up, "per_layer", "a")) == {
        "all.x", "some.y"}
    assert set(cell_lib.metrics_for(made_up, "per_layer", "c")) == {"all.x"}
    manifest = TREES[tree]
    for kind in ("end_to_end", "per_layer"):
        for cell in manifest["workloads"]:
            got = cell_lib.metrics_for(manifest, kind, cell["name"])
            for m in manifest[kind]:
                listed = m.get("workloads")
                assert (m["name"] in got) == (listed is None
                                              or cell["name"] in listed)
    narrowed = [m for m in manifest["per_layer"] if "workloads" in m]
    assert narrowed and all(m["workloads"] for m in narrowed)

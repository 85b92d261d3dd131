"""BENCHMARK.json against the files it names, and against its own rules."""

import json
import os
import re
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_tiny import REPO  # noqa: E402

from benchmark.lib import cell as cell_lib  # noqa: E402

MANIFEST = cell_lib.load_json(os.path.join(REPO, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = MANIFEST["workloads"]
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
BENCH = os.path.join(REPO, "benchmark")


def _traffic(cell):
    return cell_lib.load_json(
        os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))


def _reader(name):
    return cell_lib.load_module(
        os.path.join(BENCH, "layers", name + ".py"), f"layer_{name}")


def test_top_level_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["benchmark", "tests/benchmark"]
    assert MANIFEST["command"] == ["python3", "benchmark/run.py"]
    assert isinstance(MANIFEST["run_seconds"], int)
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024
    four = [c for c in CELLS if c["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 4)
    assert [c["name"] for c in four] == ["bert-large.collective.4chip"]


@pytest.mark.parametrize("config", MANIFEST["configs"],
                         ids=lambda c: c["name"])
def test_configuration_files(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"]) and len(config["why"]) <= 200
    assert config["source"].startswith("https://")
    assert config["file"] == f"benchmark/configs/{config['name']}.json"
    body = cell_lib.load_json(os.path.join(REPO, config["file"]))
    assert body["source"] == config["source"]
    assert body["reduced"] == config["reduced"] == []
    module = cell_lib.load_module(
        os.path.join(REPO, config["file"][:-5] + ".py"), "cfg")
    for fn in ("build", "make_batch", "reference_weights", "reference_loss",
               "flops_per_token"):
        assert callable(getattr(module, fn)), fn
    assert any(c["config"] == config["name"] for c in CELLS)
    assert (body["tokens_per_step_per_chip"]
            == body["batch_per_chip"] * body["seq_len"] == 8192)


@pytest.mark.parametrize("cell", CELLS, ids=lambda c: c["name"])
def test_cell_files_found_by_name(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    assert cell["config"] in {c["name"] for c in MANIFEST["configs"]}
    traffic = _traffic(cell)
    assert traffic["chips"] == cell["chips"] and cell["chips"] in (1, 4)
    assert traffic["mode"] in ("collective", "ps")
    assert callable(cell_lib.resolve(traffic["step_builder"]))
    assert traffic["guarantees"]
    # the cell reports setup_s, another end-to-end metric and, through its
    # readers, at least one per-layer metric the manifest lists for it
    assert len(cell_lib.metrics_for(MANIFEST, "end_to_end",
                                    cell["name"])) >= 2
    listed = set(cell_lib.metrics_for(MANIFEST, "per_layer", cell["name"]))
    declared = set()
    for reader in traffic["readers"]:
        declared |= set(_reader(reader).METRICS)
    assert listed and listed <= declared, listed - declared


def test_cells_and_pairs_are_unique():
    assert len({c["name"] for c in CELLS}) == len(CELLS) == 4
    assert len({(c["config"], c["traffic"]) for c in CELLS}) == len(CELLS)
    assert len({m["name"] for m in METRICS}) == len(METRICS)


@pytest.mark.parametrize("metric", MANIFEST["end_to_end"],
                         ids=lambda m: m["name"])
def test_end_to_end_metric(metric):
    assert set(metric) <= {"name", "unit", "better", "bound", "source",
                           "workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.1


def test_the_five_end_to_end_metrics():
    assert [m["name"] for m in MANIFEST["end_to_end"]] == [
        "tokens_per_s_per_chip", "step_ms_p50", "mfu_pct", "peak_hbm_gb",
        "setup_s"]


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader_that_declares_it(metric):
    assert set(metric) <= {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["moves"] in {m["name"] for m in MANIFEST["end_to_end"]}
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    for name in metric.get("workloads", []):
        assert name in {c["name"] for c in CELLS}
    reader = _reader(metric["name"].split(".")[0])
    declared = reader.METRICS[metric["name"]]
    assert reader.LAYER == metric["layer"]
    assert {k: metric[k] for k in declared} == declared


def test_paths_hold_only_well_named_files():
    allowed = re.compile(r"^[A-Za-z0-9_./-]+$")
    for path in MANIFEST["paths"]:
        for root, dirs, files in os.walk(os.path.join(REPO, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(root, f), REPO)
                assert allowed.match(rel), rel


def test_peaks_are_keyed_by_device_kind_and_unknown_is_an_error():
    from benchmark.lib import device

    assert device.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    for kind in ("cpu", "TPU v4", "_source"):
        with pytest.raises(device.DeviceError, match="no published peaks"):
            device.peaks(kind)


def test_result_line_keys_are_the_contract_s(tmp_path):
    """What run.py prints is json.dumps of run_cell's dict: its keys are
    checked in the rehearsals; here, that metrics_for follows `workloads`."""
    ps = cell_lib.metrics_for(MANIFEST, "per_layer", "gpt2-124m.ps.1chip")
    col = cell_lib.metrics_for(MANIFEST, "per_layer",
                               "gpt2-124m.collective.1chip")
    four = cell_lib.metrics_for(MANIFEST, "per_layer",
                                "bert-large.collective.4chip")
    assert "ccore.round_wall_ms" in ps and "ccore.round_wall_ms" not in col
    assert "ici.exposed_ms" in four and "ici.exposed_ms" not in ps
    assert set(col) == {"step.device_ms", "step.programs_per_step",
                        "device.idle_pct", "setup.compile_s"}
    assert json.dumps(sorted(four)) != json.dumps(sorted(col))

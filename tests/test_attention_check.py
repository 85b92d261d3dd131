"""``tools/attention_check.py`` at a small size, the kernels interpreted:
the check the chip runs at the Laguna cell's shapes passes for the sound
kernel and fails for a band one key off, for the wrong head grouping and
for bf16 logits."""

import importlib
import importlib.util
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def tool():
    spec = importlib.util.spec_from_file_location(
        "attention_check", os.path.join(REPO, "tools", "attention_check.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _interpreted(q, k, v, window):
    fa = importlib.import_module("byteps_tpu.ops.flash_attention")
    # blocks of 32 x 64: the band crosses blocks
    return fa.flash_attention(q, k, v, True, None, 32, 64, True, window)


@pytest.mark.parametrize("name, window, controls", [
    ("windowed", 24, {"window_minus_1", "window_plus_1",
                      "heads_interleaved"}),
    ("global", None, {"heads_interleaved"}),
])
def test_the_check_passes_the_kernel_and_fails_its_controls(
        tool, name, window, controls):
    record = tool.check(tool.Case(name, 96, 6, 2, 16, window), seed=0,
                        attend=_interpreted)
    assert record["ok"], record
    assert set(record["kernel"]) == set(tool.TENSORS)
    assert max(record["kernel"].values()) <= tool.TOLERANCE
    assert set(record["controls"]) == controls
    for control in record["controls"].values():
        assert max(control.values()) > tool.TOLERANCE
    assert record["kernel"]["out"] <= tool.OUT_TOLERANCE
    assert record["bf16_probabilities"]["out"] <= tool.OUT_TOLERANCE
    assert record["bf16_logits_and_statistics"]["out"] > tool.OUT_TOLERANCE


def test_a_wrong_kernel_fails_the_check(tool):
    """The program computed wrongly (a window one key long) reads above the
    tolerance, so ``ok`` is false."""
    record = tool.check(
        tool.Case("windowed", 96, 6, 2, 16, 24), seed=1,
        attend=lambda q, k, v, window: _interpreted(q, k, v, window + 1))
    assert not record["ok"]
    assert max(record["kernel"].values()) > tool.TOLERANCE


def test_the_cell_cases_are_the_configurations(tool):
    import json

    cfg = json.load(open(os.path.join(
        REPO, "benchmark", "configs", "laguna-xs.2.json")))
    n = cfg["num_hidden_layers"]
    kinds = dict(zip(cfg["layer_types"][:n],
                     cfg["num_attention_heads_per_layer"][:n]))
    for case in tool.CELL_CASES:
        windowed = case.window is not None
        assert case.seq == cfg["seq_len"]
        assert case.head_dim == cfg["head_dim"]
        assert case.kv_heads == cfg["num_key_value_heads"]
        assert case.heads == kinds[
            "sliding_attention" if windowed else "full_attention"]
        assert case.window == (cfg["sliding_window"] if windowed else None)

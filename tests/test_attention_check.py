"""``tools/attention_check.py`` at a small size, the kernels interpreted:
the check the chip runs at the Laguna cell's shapes and at the latent call
of the JoyAI and Kimi-Linear cells passes for the sound kernel and fails for
a band one key off, for the wrong head grouping, for the other width's
softmax scale and for bf16 logits."""

import importlib
import importlib.util
import json
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


def _interpreted(q, k, v, window, scale=None):
    fa = importlib.import_module("byteps_tpu.ops.flash_attention")
    # blocks of 32 x 64: the band crosses blocks
    return fa.flash_attention(q, k, v, True, scale, 32, 64, True, window)


@pytest.mark.parametrize("case, controls", [
    (("windowed", 96, 6, 2, 16, 24), {"window_minus_1", "window_plus_1",
                                      "heads_interleaved"}),
    (("global", 96, 6, 2, 16, None), {"heads_interleaved"}),
    # keys 24 wide, values 16: out and dV take the value width
    (("latent", 96, 4, 4, 24, None, 16), {"scale_of_value_width"}),
    # three sequences in the call, as many key heads as query heads
    (("dense", 96, 3, 3, 16, None, None, 3), {"next_key_head"}),
    # the Mellum2 cell's: 4 query heads a key head, two sequences, a band
    # of two of the kernel's key blocks
    (("windowed_1024", 160, 8, 2, 16, 128, None, 2), {
        "window_minus_1", "window_plus_1", "heads_interleaved"}),
    (("global_32_over_4", 96, 8, 2, 16, None, None, 2),
     {"heads_interleaved"}),
    # the Phi-4-mini-flash cell's: two query heads a key head
    (("dattn_windowed_512", 160, 8, 4, 16, 48), {
        "window_minus_1", "window_plus_1", "heads_interleaved"}),
    (("dattn_causal", 96, 8, 4, 16, None), {"heads_interleaved"}),
], ids=lambda value: value[0] if isinstance(value, tuple) else "")
def test_the_check_passes_the_kernel_and_fails_its_controls(
        tool, case, controls):
    record = tool.check(tool.Case(*case), seed=0, attend=_interpreted)
    assert record["ok"], record
    assert set(record["kernel"]) == set(tool.TENSORS)
    assert max(record["kernel"].values()) <= tool.TOLERANCE
    assert set(record["controls"]) == controls
    for control in record["controls"].values():
        assert max(control.values()) > tool.TOLERANCE
    assert record["kernel"]["out"] <= tool.OUT_TOLERANCE
    assert record["bf16_probabilities"]["out"] <= tool.OUT_TOLERANCE
    assert record["bf16_logits_and_statistics"]["out"] > tool.OUT_TOLERANCE


def test_a_kernel_with_the_value_width_in_its_scale_fails_the_check(tool):
    """The program computed wrongly at two widths (16^-1/2 where the keys
    are 24 wide) reads above the tolerance."""
    record = tool.check(
        tool.Case("latent", 96, 4, 4, 24, None, 16), seed=1,
        attend=lambda q, k, v, window: _interpreted(q, k, v, window,
                                                    scale=16 ** -0.5))
    assert not record["ok"]
    assert max(record["kernel"].values()) > tool.TOLERANCE


def test_a_wrong_kernel_fails_the_check(tool):
    """The program computed wrongly (a window one key long) reads above the
    tolerance, so ``ok`` is false."""
    record = tool.check(
        tool.Case("windowed", 96, 6, 2, 16, 24), seed=1,
        attend=lambda q, k, v, window: _interpreted(q, k, v, window + 1))
    assert not record["ok"]
    assert max(record["kernel"].values()) > tool.TOLERANCE


def _config(name):
    with open(os.path.join(REPO, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["joyai-llm-flash", "kimi-linear-48b-a3b"])
def test_the_latent_case_is_the_configurations(tool, name):
    cfg = _config(name)
    case, = (c for c in tool.CELL_CASES if c.name == "latent")
    assert case.seq == cfg["seq_len"]
    assert case.heads == cfg["num_attention_heads"]
    assert case.kv_heads == cfg["num_key_value_heads"]
    assert case.head_dim == (cfg["qk_nope_head_dim"]
                             + cfg["qk_rope_head_dim"])
    assert case.value_dim == cfg["v_head_dim"]
    assert case.window is None


def test_the_cell_cases_are_the_configurations(tool):
    cfg = _config("laguna-xs.2")
    n = cfg["num_hidden_layers"]
    kinds = dict(zip(cfg["layer_types"][:n],
                     cfg["num_attention_heads_per_layer"][:n]))
    for case in (c for c in tool.CELL_CASES
                 if c.name in ("windowed", "global")):
        windowed = case.window is not None
        assert case.seq == cfg["seq_len"]
        assert case.head_dim == cfg["head_dim"]
        assert case.kv_heads == cfg["num_key_value_heads"]
        assert case.heads == kinds[
            "sliding_attention" if windowed else "full_attention"]
        assert case.window == (cfg["sliding_window"] if windowed else None)


def test_the_mellum_cases_are_the_configuration_s(tool):
    cfg = _config("mellum2-12b-a2.5b")
    windowed, full = (c for c in tool.CELL_CASES
                      if c.name in ("windowed_1024", "global_32_over_4"))
    for case in (windowed, full):
        assert (case.batch, case.seq) == (cfg["batch_per_chip"],
                                          cfg["seq_len"])
        assert (case.heads, case.kv_heads, case.head_dim) == (
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"])
        assert case.value_dim is None
    assert (windowed.window, full.window) == (cfg["sliding_window"], None)
    assert set(cfg["layer_types"]) == {"sliding_attention", "full_attention"}


def test_the_differential_cases_are_the_configuration_s(tool):
    cfg = _config("phi-4-mini-flash-reasoning")
    windowed, full = (c for c in tool.CELL_CASES
                      if c.name in ("dattn_windowed_512", "dattn_causal"))
    for case in (windowed, full):
        assert (case.batch, case.seq) == (cfg["batch_per_chip"],
                                          cfg["seq_len"])
        assert (case.heads, case.kv_heads, case.head_dim) == (
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["hidden_size"] // cfg["num_attention_heads"])
        assert case.value_dim is None
    assert (windowed.window, full.window) == (cfg["sliding_window"], None)


def test_the_dense_case_is_the_gpt2_configuration(tool):
    cfg = _config("gpt2-124m")
    case, = (c for c in tool.CELL_CASES if c.name == "dense")
    assert (case.batch, case.seq) == (cfg["batch_per_chip"], cfg["seq_len"])
    assert case.heads == case.kv_heads == cfg["n_head"]
    assert case.head_dim == cfg["n_embd"] // cfg["n_head"]
    assert case.window is None and case.value_dim is None


def test_a_kernel_that_mixes_a_batch_s_sequences_fails_the_check(tool):
    """The program computed wrongly (the sequences of the call answered in
    another order) reads above the tolerance."""
    record = tool.check(
        tool.Case("dense", 96, 3, 3, 16, None, None, 3), seed=1,
        attend=lambda q, k, v, window: _interpreted(q, k, v, window)[::-1])
    assert not record["ok"]
    assert max(record["kernel"].values()) > tool.TOLERANCE


# --------------------------------------------------------------------------
# the ZAYA1 cell's mixer (PR 55)

def test_the_mixer_check_passes_the_mixer_and_fails_its_five_controls(tool):
    """A mixer of 4 query heads over 2 key heads of 128 (the published
    width: a running sum of 128 squares is what a bf16 normalisation loses
    most of), 64 tokens, float32: the program reads at float32's rounding
    and every control — the value shift dropped, conv1's taps swapped, the
    q-k mean left out, the temperature ignored, a bf16 normalisation — over
    the tolerance."""
    import jax.numpy as jnp

    record = tool.check_cca(0, tool.Mixer(64, 128, 4, 2, 128, 5e6, 0.5),
                            dtype=jnp.float32)
    assert record["ok"], record
    assert set(record["mixer"]) == set(tool.CCA_TENSORS)
    assert max(record["mixer"].values()) <= 1e-4
    assert set(record["controls"]) == set(tool.CCA_CONTROLS) and len(
        record["controls"]) == 5
    for name, control in record["controls"].items():
        assert max(control.values()) > tool.CCA_TOLERANCE, name


def test_a_mixer_that_turns_the_whole_head_fails_the_check(tool, monkeypatch):
    """The program computed wrongly (all 128 entries of a head rotated where
    the reference rotates 64) reads above the tolerance."""
    import jax.numpy as jnp

    zaya = importlib.import_module("byteps_tpu.models.zaya")
    layer = zaya.CompressedConvAttention
    monkeypatch.setattr(
        zaya, "CompressedConvAttention",
        lambda heads, kv, d, theta, factor, **kw: layer(
            heads, kv, d, theta, 1.0, **kw))
    record = tool.check_cca(1, tool.Mixer(64, 128, 4, 2, 128, 5e6, 0.5),
                            dtype=jnp.float32)
    assert not record["ok"]
    assert max(record["mixer"].values()) > tool.CCA_TOLERANCE


def test_the_mixer_case_is_the_configuration_s(tool):
    cfg = _config("zaya1-8b")
    rope = cfg["rope_parameters"]["hybrid"]
    assert tool.CCA_CELL == tool.Mixer(
        cfg["seq_len"], cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"], rope["rope_theta"],
        rope["partial_rotary_factor"])

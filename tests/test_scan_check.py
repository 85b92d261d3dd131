"""``tools/scan_check.py`` at a small size on the CPU: the check the chip
runs at the Qwen3-Next cell's shapes passes for the sound scan and fails for
a bf16 state, a clamped decay and the other head grouping, and so does its
per-channel twin at the Kimi-Linear cell's shapes (a bf16 state, a clamped
decay) and the scan with no delta rule at the Nemotron cell's (the same
three controls); the 256-wide attention case is
``tools/attention_check.py``'s own check."""

import importlib
import json
import os

import jax.numpy as jnp
import pytest

from tools import scan_check as tool

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMALL = tool.ScanCase(1024, 2, 4, 64, 64, 64)
SMALL_CHANNEL = tool.ChannelCase(512, 4, 32, 32, 32, 8)


@pytest.mark.parametrize("seed", (0, 2147483907))
def test_the_check_passes_the_scan_and_fails_its_controls(seed):
    record = tool.check_scan(SMALL, seed)
    assert record["ok"], record
    assert set(record["scan"]) == set(tool.TENSORS)
    assert max(record["scan"].values()) <= tool.SCAN_TOLERANCE
    assert set(record["controls"]) == {"bf16_state", "heads_interleaved",
                                       "clamped_at_-20"}
    for control in record["controls"].values():
        assert max(control.values()) > tool.SCAN_TOLERANCE
    assert record["min_chunk_log_decay"] < tool.CLAMP


@pytest.mark.parametrize("seed", (0, 2147483907))
def test_the_per_channel_check_passes_the_scan_and_fails_its_controls(seed):
    record = tool.check_scan(SMALL_CHANNEL, seed)
    assert record["ok"], record
    assert set(record["scan"]) == set(tool.TENSORS)
    assert max(record["scan"].values()) <= tool.SCAN_TOLERANCE
    assert set(record["controls"]) == {"bf16_state", "clamped_at_-20"}
    for control in record["controls"].values():
        assert max(control.values()) > tool.SCAN_TOLERANCE
    assert record["min_chunk_log_decay"] < tool.CLAMP


SMALL_SSD = tool.SsdCase(1024, 2, 4, 32, 16, 64)


@pytest.mark.parametrize("seed", (0, 2147483907))
def test_the_state_space_check_passes_the_scan_and_fails_its_controls(seed):
    record = tool.check_ssd(SMALL_SSD, seed)
    assert record["ok"], record
    assert set(record["scan"]) == set(tool.SSD_TENSORS)
    assert max(record["scan"].values()) <= tool.SCAN_TOLERANCE
    assert set(record["controls"]) == {"bf16_state", "heads_interleaved",
                                       "clamped_at_-20"}
    for control in record["controls"].values():
        assert max(control.values()) > tool.SCAN_TOLERANCE
    assert record["min_chunk_log_decay"] < tool.CLAMP


def test_a_wrong_state_space_scan_fails_the_check():
    """The program computed wrongly (the write unscaled by the step) reads
    above the tolerance, so ``ok`` is false."""
    from byteps_tpu.parallel.linear_attention import ssd_scan

    def unscaled(x, b, c, dt, a_log):
        return ssd_scan(c, b, x, -jnp.exp(a_log) * dt, jnp.ones_like(dt),
                        chunk=SMALL_SSD.chunk)

    record = tool.check_ssd(SMALL_SSD, 1, scan=unscaled)
    assert not record["ok"]
    assert max(record["scan"].values()) > tool.SCAN_TOLERANCE


def test_the_state_space_case_is_the_configuration_s():
    cfg = json.load(open(os.path.join(
        REPO, "benchmark", "configs", "nemotron-3-nano-30b-a3b.json")))
    assert tool.ssd_case() == (cfg["seq_len"], 8, 64, 128, 64,
                               cfg["ssm_chunk"])
    x, b, c, dt, a_log, w = tool.ssd_inputs(SMALL_SSD, 0)
    assert x.shape == w.shape == (1, 1024, 4, 16)
    assert b.shape == c.shape == (1, 1024, 2, 32)
    assert dt.shape == (1, 1024, 4) and a_log.shape == (4,)
    assert float(dt.min()) > 0.0


def test_a_wrong_scan_fails_the_check():
    """The program computed wrongly (its state's decay rounded to bf16)
    reads above the tolerance, so ``ok`` is false."""
    from byteps_tpu.parallel.linear_attention import kda_attention

    def rounded(q, k, v, g, beta):
        return kda_attention(q, k, v, g.astype(jnp.bfloat16).astype(
            jnp.float32) * 1.1, beta, chunk=SMALL.chunk, sub=SMALL.chunk)

    record = tool.check_scan(SMALL, 1, scan=rounded)
    assert not record["ok"]
    assert max(record["scan"].values()) > tool.SCAN_TOLERANCE


def test_the_cell_cases_are_the_configuration_s():
    cfg = json.load(open(os.path.join(
        REPO, "benchmark", "configs", "qwen3-next-80b-a3b.json")))
    scan, attention = tool.cell_cases()
    assert scan == (cfg["seq_len"], 16, 32, 128, 128, cfg["gdn_chunk"])
    assert attention[1:] == (cfg["seq_len"], 16, 2, 256, None, None, 1)


def test_the_channel_case_is_the_configuration_s():
    cfg = json.load(open(os.path.join(
        REPO, "benchmark", "configs", "kimi-linear-48b-a3b.json")))
    assert tool.channel_case() == (cfg["seq_len"], 32, 128, 128, 32, 8)
    q, k, v, g, beta, w = tool.scan_inputs(SMALL_CHANNEL, 0)
    assert q.shape == k.shape == g.shape == (1, 512, 4, 32)
    assert v.shape == w.shape == (1, 512, 4, 32) and beta.shape == (1, 512, 4)
    assert float(g.max()) < 0.0


def test_the_attention_case_at_a_small_size():
    """8 query heads a key head, as the cell's: the interpreted kernels pass
    ``attention_check``'s check and its two controls fail it."""
    fa = importlib.import_module("byteps_tpu.ops.flash_attention")
    record = tool.attention_check.check(
        tool.attention_check.Case("gated", 96, 16, 2, 32, None), seed=0,
        attend=lambda q, k, v, window: fa.flash_attention(
            q, k, v, True, None, 32, 64, True, window))
    assert record["ok"], record
    assert set(record["controls"]) == {"heads_interleaved"}


SMALL_CONVS = [
    tool.ConvCase("bf16_bias_silu", 512, 256, 4, "bfloat16", True, "silu"),
    tool.ConvCase("bf16_silu", 512, 128, 4, "bfloat16", False, "silu"),
    tool.ConvCase("f32_plain", 512, 256, 2, "float32", False, None),
]


@pytest.mark.parametrize("case", SMALL_CONVS, ids=[c.name for c in
                                                   SMALL_CONVS])
@pytest.mark.parametrize("seed", (0, 2147483907))
def test_the_convolution_check_passes_the_kernel_and_fails_its_controls(
        monkeypatch, case, seed):
    """``causal_conv`` told it is on a TPU: the kernel interpreted, one
    block of 512 rows, under the rule with the XLA form's backward pass."""
    import byteps_tpu.models.kimi_linear as kl

    monkeypatch.setattr(kl, "conv_form", lambda *shapes: "kernel")
    record = tool.check_conv(case, seed)
    assert record["ok"], record
    assert record["form"] == "kernel" and record["kernel_in_program"]
    assert record["seeds"] == tool.CONV_SEEDS
    names = {"y", "dx", "dw"} | ({"dbias"} if case.biased else set())
    assert set(record["conv"]) == set(record["tolerance"]) == names
    assert set(record["controls"]) == {"taps_reversed", "bf16_output"}
    for reading in record["controls"].values():
        assert reading > tool.CONV_TOLERANCE["y"]


@pytest.mark.parametrize("wrong", ["a_tap_dropped", "a_token_late"])
def test_a_wrong_convolution_fails_the_check(wrong):
    from byteps_tpu.models.kimi_linear import causal_conv_xla

    def conv(x, w, bias):
        if wrong == "a_tap_dropped":
            return causal_conv_xla(x, w.at[0].set(0.0), bias, "silu")
        return causal_conv_xla(jnp.roll(x, 1, axis=1), w, bias, "silu")

    record = tool.check_conv(SMALL_CONVS[0], 1, conv=conv)
    assert not record["ok"]
    assert record["conv"]["y"] > tool.CONV_TOLERANCE["y"]


SMALL_GATES = [tool.GateCase(512, 4, 64, 2, 16),
               tool.GateCase(1024, 8, 64, 1, 32)]


@pytest.mark.parametrize("case", SMALL_GATES, ids=["two_groups", "one_group"])
@pytest.mark.parametrize("seed", (0, 2147483907))
def test_the_gate_check_passes_the_kernels_and_fails_its_controls(
        monkeypatch, case, seed):
    """``gated_group_norm`` told it is on a TPU: the kernel pair
    interpreted, blocks of 512 rows, ``x`` and ``z`` read where they lie."""
    import byteps_tpu.models.nemotron_h as nh

    monkeypatch.setattr(nh, "gate_form", lambda *shapes: "kernel")
    record = tool.check_gate(case, seed)
    assert record["ok"], record
    assert record["form"] == "kernel" and record["kernel_in_program"]
    assert set(record["gate"]) == set(tool.GATE_TENSORS)
    assert set(record["tolerance"]) == set(tool.GATE_TENSORS) | {"rounding"}
    # one rounding to bf16 from the float32 result, and no more
    assert tool.GATE_TOLERANCE["out"] < record["rounding"] \
        <= tool.GATE_ROUNDING
    assert set(record["controls"]) == {
        "norm_before_gate", "bf16_gated_product"} | (
            {"groups_twice_as_wide"} if case.groups > 1 else set())
    for reading in record["controls"].values():
        assert reading > tool.GATE_TOLERANCE["out"]


def test_the_gate_check_passes_the_xla_form_on_the_cpu():
    record = tool.check_gate(SMALL_GATES[0], 1)
    assert record["ok"] and record["form"] == "xla", record
    assert max(record["gate"].values()) == 0.0


@pytest.mark.parametrize("wrong", ["no_skip", "eps_1e-3", "a_token_late",
                                   "float32_result"])
def test_a_wrong_chain_fails_the_gate_check(wrong):
    from byteps_tpu.models.nemotron_h import gated_group_norm_xla

    case = SMALL_GATES[0]
    inner = case.heads * case.head_dim

    def chain(y, mixed, z, skip, weight):
        kw = {"groups": case.groups, "head_dim": case.head_dim}
        x = mixed[..., :inner]
        if wrong == "no_skip":
            skip = jnp.zeros_like(skip)
        elif wrong == "eps_1e-3":
            kw["eps"] = 1e-3
        elif wrong == "a_token_late":
            z = jnp.roll(z, 1, axis=1)
        else:
            kw["dtype"] = jnp.float32
        return gated_group_norm_xla(y, x, z, skip, weight, **kw)

    record = tool.check_gate(case, 1, chain=chain)
    assert not record["ok"]
    assert record["gate"]["out"] > tool.GATE_TOLERANCE["out"]


def test_the_gate_case_is_the_configuration():
    from byteps_tpu.models.nemotron_h import gate_form

    case = tool.gate_case()
    assert tuple(case) == (16384, 64, 64, 8, 128)
    assert gate_form("tpu", case.seq, case.heads * case.head_dim, case.groups,
                     case.head_dim, jnp.bfloat16) == "kernel"


def test_the_convolution_cases_are_the_configurations():
    assert [tuple(c) for c in tool.conv_cases()] == [
        ("nemotron", 16384, 6144, 4, "bfloat16", True, "silu"),
        ("qwen3_next", 16384, 8192, 4, "bfloat16", False, "silu"),
        ("kimi_linear", 8192, 4096, 4, "bfloat16", False, "silu"),
        ("zaya1", 16384, 1280, 2, "float32", False, None)]
    from byteps_tpu.models.kimi_linear import conv_form
    for case in tool.conv_cases():
        assert conv_form("tpu", case.seq, case.channels,
                         case.taps) == "kernel"


SMALL_SEL = tool.SelCase(512, 24, 4, 16)


@pytest.mark.parametrize("seed", (0, 2147483907))
def test_the_selective_check_passes_the_scan_and_fails_its_controls(seed):
    """The scan with one decay a channel and state entry at a small size:
    the program's form within ``SEL_TOLERANCE`` in ``y`` and all five
    gradients, and a bf16 state, a chunk's cumulated log-decay clamped at
    -20 and one decay a channel each over it."""
    record = tool.check_sel(SMALL_SEL, seed)
    assert record["ok"], record
    assert set(record["scan"]) == set(tool.SEL_TENSORS)
    assert max(record["scan"].values()) <= tool.SEL_TOLERANCE
    assert set(record["controls"]) == {"bf16_state", "clamped_at_-20",
                                       "one_decay_a_channel"}
    for control in record["controls"].values():
        assert max(control.values()) > tool.SEL_TOLERANCE
    assert record["min_chunk_log_decay"] < tool.CLAMP


def test_a_wrong_selective_scan_fails_the_check():
    """The program computed wrongly (its state entries read one decay a
    channel, the first entry's) reads above the tolerance."""
    from byteps_tpu.parallel.linear_attention import selective_scan

    record = tool.check_sel(
        SMALL_SEL, 1, scan=lambda x, dt, a, b, c: selective_scan(
            x, dt, jnp.broadcast_to(a[:, :1], a.shape), b, c))
    assert not record["ok"]
    assert max(record["scan"].values()) > tool.SEL_TOLERANCE


def test_the_selective_case_is_the_configuration_s():
    from byteps_tpu.parallel.linear_attention import SEL_CHUNK

    cfg = json.load(open(os.path.join(
        REPO, "benchmark", "configs", "phi-4-mini-flash-reasoning.json")))
    assert tool.sel_case() == (cfg["seq_len"], 5120, 16, SEL_CHUNK)
    x, dt, a, b, c, w = tool.sel_inputs(SMALL_SEL, 0)
    assert x.shape == dt.shape == w.shape == (1, 512, 24)
    assert b.shape == c.shape == (1, 512, 4) and a.shape == (24, 4)
    assert float(dt.min()) > 0.0 and float(a.max()) <= -1.0

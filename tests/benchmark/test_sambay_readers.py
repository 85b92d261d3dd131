"""The three readers of ``phi-4-mini-flash-reasoning.collective-sambay.
1chip`` (``benchmark/layers/sel.py``, ``dattn.py``, ``gmu.py``): the
rooflines' operations and bytes by hand at the cell's size, and their
reading of a made-up ``.xplane.pb`` (encoded by ``test_moe_reader.py``'s
helpers, with hand-worked sums) through the one shared read of the capture,
the probe's gauge, and what a program without the scopes gets. No JAX."""

import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
from bench_tiny import REPO  # noqa: E402,F401
from test_moe_reader import MS, _capture, _plane  # noqa: E402

from benchmark.layers import dattn, gmu, sel, swa  # noqa: E402
from benchmark.lib import cell as cell_lib  # noqa: E402
from benchmark.lib import trace_reduce as tr  # noqa: E402

V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "phi-4-mini-flash-reasoning.collective-sambay.1chip"
CFG = {**cell_lib.load_json(os.path.join(
    REPO, "benchmark", "configs", "phi-4-mini-flash-reasoning.json")),
    "seq_len": 16384}
ROWS = CFG["batch_per_chip"]
CONFIG = cell_lib.load_module(os.path.join(
    REPO, "benchmark", "configs", "phi-4-mini-flash-reasoning.py"),
    "phi4_flash_config")


def test_the_scan_s_roofline_by_hand():
    """``layers/sel.py``'s docstring: 5 operations a token, channel and
    state entry forward and twice that backward; x, Delta, y a channel and
    B, C a state entry in float32 once each way; two Mamba-1 layers; bound
    by bandwidth."""
    assert (ROWS, CFG["layer_indices"], CFG["mamba_d_state"],
            CFG["mamba_expand"] * CFG["hidden_size"]) == (
        1, [0, 1, 16, 17, 18, 19], 16, 5120)
    assert CONFIG.layer_counts(CFG)["mamba"] == 2
    assert CONFIG.layer_counts(
        {**CFG, "layer_indices": list(range(32))})["mamba"] == 9
    flops = sel.scan_flops(16384, 5120, 16, 2)
    moved = sel.scan_bytes(16384, 5120, 16, 2)
    assert flops == 3 * 2 * 16384 * 5120 * 16 * 5 == 40_265_318_400
    assert moved == 2 * 2 * 16384 * 4 * (3 * 5120 + 2 * 16) == 4_034_920_448
    flops_ms, bytes_ms = 1e3 * flops / 197e12, 1e3 * moved / 819e9
    assert flops_ms == pytest.approx(0.2044, abs=1e-4)
    assert bytes_ms == pytest.approx(4.9266, abs=1e-4)   # bandwidth binds
    assert sel.scan_roofline_pct(100.0, CFG, 16384, 2, V5E) == \
        pytest.approx(bytes_ms)
    assert sel.scan_roofline_pct(bytes_ms, CFG, 16384, 2, V5E) == \
        pytest.approx(100.0)


def test_the_two_attention_rooflines_by_hand():
    """``layers/dattn.py``'s docstring: a pair of a pair of heads costs 768
    operations forward (two scores of 64, two maps over a value of 128) and
    2,304 with the backward pass; 20 pairs; the band of one windowed layer,
    the triangle of the full and the cross layer; both bound by
    arithmetic."""
    s = 16384
    assert CONFIG.layer_counts(CFG) == {
        "mamba": 2, "window": 1, "full": 1, "cross": 1, "gmu": 1}
    assert CONFIG.layer_counts(
        {**CFG, "layer_indices": list(range(32))}) == {
            "mamba": 9, "window": 8, "full": 1, "cross": 7, "gmu": 7}
    band, triangle = swa.needed_pairs(s, 512), swa.needed_pairs(s)
    assert (band, triangle) == (8_257_792, 134_225_920)
    assert dattn.attend_flops(ROWS, s, 20, 64, 512) == band * 20 * 2304 \
        == 380_519_055_360
    assert dattn.attend_flops(ROWS, s, 20, 64) == triangle * 20 * 2304 \
        == 6_185_130_393_600
    layer_bytes = swa.attend_bytes(ROWS, s, 40, 20, 64)
    assert layer_bytes == 2 * 2 * s * (2 * 40 + 2 * 20) * 64 == 503_316_480
    window_ms = 1e3 * band * 46_080 / 197e12
    full_ms = 1e3 * 2 * triangle * 46_080 / 197e12
    assert window_ms == pytest.approx(1.9316, abs=1e-4)
    assert full_ms == pytest.approx(62.793, abs=1e-3)
    assert 1e3 * layer_bytes / 819e9 < window_ms       # arithmetic binds
    assert dattn.roofline_pct(10.0, CFG, ROWS, V5E, 1, 512) == \
        pytest.approx(100 * window_ms / 10.0)
    assert dattn.roofline_pct(300.0, CFG, ROWS, V5E, 2) == pytest.approx(
        100 * full_ms / 300.0)


FWD = "jit(_step)/jvp(Phi4FlashModel)/layer_%d_mixer/"
BACK = "jit(_step)/transpose(jvp(Phi4FlashModel))/layer_%d_mixer/"
OPS = [
    # the scan's loop over chunks is a container: what is under it counts
    ("%while.12 = (s32[], f32[1,16,5120]) while(...)",
     FWD % 0 + "ssm/bps.sel.scan/while:", "str", [30 * MS] * 2),
    ("%fusion.40 = f32[1,16,5120]{2,1,0} fusion(...)",
     FWD % 0 + "ssm/bps.sel.scan/while/body/closed_call/while/body/mul:",
     "str", [12 * MS] * 2),
    ("%fusion.41 = f32[1,16,5120]{2,1,0} fusion(...)",
     BACK % 16 + "checkpoint/ssm/bps.sel.scan/while/body/checkpoint/"
     "while/body/mul:", "ref", [20 * MS] * 2),
    ("%bps_causal_conv_fwd.1 = f32[1,16384,5120] custom-call(...)",
     FWD % 16 + "ssm/checkpoint/bps.sel.prep/pallas_call:", "str",
     [3 * MS] * 2),
    ("%fusion.42 = bf16[16384,10240]{1,0} fusion(...)",
     FWD % 0 + "ssm/bps.sel.proj/in/dot_general:", "str", [7 * MS] * 2),
    ("%fusion.43 = bf16[1,16384,5120]{2,1,0} fusion(...)",
     FWD % 16 + "ssm/bps.sel.out/mul:", "str", [2 * MS] * 2),
    ("%bps_flash_fwd.3 = (bf16[40,16384,64]) custom-call(...)",
     FWD % 1 + "attn/bps.dattn.window/bps.attn.kernel/pallas_call:", "str",
     [4 * MS] * 2),
    ("%bps_flash_bwd.3 = (bf16[40,16384,64]) custom-call(...)",
     BACK % 1 + "checkpoint/attn/bps.dattn.window/bps.attn.kernel/"
     "jit(_flash_bwd_impl)/pallas_call:", "ref", [6 * MS] * 2),
    ("%bps_flash_fwd.4 = (bf16[40,16384,64]) custom-call(...)",
     FWD % 17 + "attn/bps.dattn.full/bps.attn.kernel/pallas_call:", "str",
     [50 * MS] * 2),
    ("%bps_flash_bwd.4 = (bf16[40,16384,64]) custom-call(...)",
     BACK % 19 + "checkpoint/attn/bps.dattn.cross/bps.attn.kernel/"
     "jit(_flash_bwd_impl)/pallas_call:", "ref", [75 * MS] * 2),
    ("%fusion.44 = bf16[16384,2560]{1,0} fusion(...)",
     FWD % 19 + "attn/bps.dattn.proj/q/dot_general:", "str", [5 * MS] * 2),
    ("%fusion.45 = bf16[1,16384,20,128]{3,2,1,0} fusion(...)",
     BACK % 17 + "checkpoint/attn/bps.dattn.diff/mul:", "str",
     [3 * MS] * 2),
    ("%fusion.46 = bf16[16384,5120]{1,0} fusion(...)",
     FWD % 18 + "gmu/bps.gmu/in/dot_general:", "str", [9 * MS] * 2),
    ("%fusion.47 = f32[16384,5120]{1,0} fusion(...)",
     BACK % 18 + "checkpoint/gmu/bps.gmu/mul:", "str", [2 * MS] * 2),
    ("%fusion.54 = f32[25008,2560]{1,0} fusion(...)",
     "jit(_step)/adamw/mul:", "str", [4 * MS] * 2),
]


def _run(tmp_path, trace, **more):
    return types.SimpleNamespace(
        trace={"steps": 2}, out_dir=str(tmp_path), layout=tr.TPU,
        probes={}, config=CONFIG, cfg=dict(CFG), rows=ROWS, chips=1, **more)


def test_each_layer_counts_under_its_own_scope(tmp_path, monkeypatch):
    """Two steps; the programs' line reads 0.999 ms over them. The scan 12
    + 20 (the ``while`` above them is a container), preparation 3,
    projections 7, the output chain 2; windowed 4 + 6, full 50, cross 75,
    projections 5, the difference 3; the memory unit 9 + 2. The ratio comes
    from the counters."""
    trace = _capture(tmp_path, [_plane("/device:TPU:0", OPS)])
    monkeypatch.setattr("jax.devices", lambda: [types.SimpleNamespace(
        device_kind="TPU v5 lite")])
    monkeypatch.setattr(swa, "walked_pairs_ratio", lambda: 1.25)
    run = _run(tmp_path, trace)
    got = {**sel.read(run), **dattn.read(run), **gmu.read(run)}
    programs_ms = 999_000_000 * 1e-9 / 2               # 0.4995 ms a step
    assert got == {
        "sel.scan_ms": 32.0, "sel.prep_ms": 3.0, "sel.proj_ms": 7.0,
        "sel.layer_share_pct": pytest.approx(100 * 44.0 / programs_ms),
        "sel.scan_roofline_pct": pytest.approx(100 * 4.9266 / 32.0,
                                               abs=1e-3),
        "dattn.window_ms": 10.0, "dattn.full_ms": 50.0,
        "dattn.cross_ms": 75.0, "dattn.proj_ms": 5.0, "dattn.diff_ms": 3.0,
        "dattn.layer_share_pct": pytest.approx(100 * 143.0 / programs_ms),
        "dattn.window_roofline_pct": pytest.approx(100 * 1.9316 / 10.0,
                                                   abs=1e-3),
        "dattn.full_roofline_pct": pytest.approx(100 * 62.793 / 125.0,
                                                 abs=1e-3),
        "dattn.walked_pairs_ratio": 1.25,
        "gmu.unit_ms": 11.0}
    assert run.probes == {"sel_out_ms": 2.0}


def test_a_capture_without_the_scopes_reports_nothing(tmp_path, monkeypatch):
    """As the parent's program is: no scope, no counter, no collection; the
    readers return nothing that has a value and do not raise, traced or
    not."""
    from byteps_tpu.monitor import metrics

    monkeypatch.setattr(metrics, "counter", lambda name: 0.0)
    trace = _capture(tmp_path, [_plane("/device:TPU:0", OPS[-1:])])
    run = _run(tmp_path, trace)
    assert sel.read(run) == {} and gmu.read(run) == {}
    assert dattn.read(run) == {"dattn.walked_pairs_ratio": None}
    run.trace = None
    assert sel.read(run) == {} and gmu.read(run) == {}
    assert dattn.read(run) == {"dattn.walked_pairs_ratio": None}
    sel.setup(run)                       # no probe to run: nothing, no raise
    run.config = types.SimpleNamespace(layer_stats=None, FIRST={})
    sel.setup(run)
    assert run.probes == {}


def test_the_probe_publishes_the_smallest_chunk_log_decay(monkeypatch):
    from byteps_tpu.monitor import metrics

    published = {}
    monkeypatch.setattr(metrics, "set_gauge", published.__setitem__)
    run = types.SimpleNamespace(
        probes={}, cfg=dict(CFG), rows=ROWS, chips=1,
        config=types.SimpleNamespace(FIRST={"seed": 1}, layer_stats=(
            lambda cfg, rows: {"sel_stats": {
                "layer_0_mixer": {"ssm": {"min_chunk_log_decay": (-41.5,)}},
                "layer_16_mixer": {"ssm": {"min_chunk_log_decay": (-7.0,)}},
            }})))
    sel.setup(run)
    assert run.probes == published == {sel.GAUGE: -41.5}


@pytest.mark.parametrize("reader,prefix,layer", [
    (sel, "sel.", "selective state-space layers"),
    (dattn, "dattn.", "differential attention"),
    (gmu, "gmu.", "gated memory unit")])
def test_the_readers_declare_what_the_manifest_lists(reader, prefix, layer):
    manifest = cell_lib.load_json(os.path.join(REPO, "BENCHMARK.json"))
    listed = {m["name"]: m for m in manifest["per_layer"]
              if m["name"].startswith(prefix)}
    assert reader.LAYER == layer
    assert set(listed) == set(reader.METRICS)
    for name, metric in listed.items():
        assert metric["layer"] == reader.LAYER
        assert metric["workloads"] == [CELL]
        assert {k: metric[k] for k in ("unit", "better", "source",
                                       "moves")} == reader.METRICS[name]
        if name.endswith("_roofline_pct"):
            assert (metric["unit"], metric["better"], metric["moves"]) == (
                "%", "higher", "mfu_pct")


def test_the_scopes_and_counters_are_the_program_s():
    """Read, not imported: no JAX here."""
    def source(*path):
        with open(os.path.join(REPO, "byteps_tpu", *path)) as f:
            return f.read()

    model, scan = (source("models", "phi4_flash.py"),
                   source("parallel", "linear_attention.py"))
    for scope in (*dattn.SCOPES.values(), gmu.SCOPES["unit"],
                  sel.SCOPES["proj"], sel.SCOPES["out"]):
        assert '"%s"' % scope in model, scope
    for scope in (sel.SCOPES["scan"], sel.SCOPES["prep"]):
        assert '"%s"' % scope in scan, scope
    assert '"sel_stats"' in model
    assert '"%s"' % swa.WALKED in source("parallel", "ring_attention.py")

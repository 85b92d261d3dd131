"""The two readers of ``mellum2-12b-a2.5b.collective-swa-moe.1chip``
(``benchmark/layers/mswa.py``, ``eshare.py``): the rooflines' operations and
bytes by hand at the cell's size, their reading of a made-up ``.xplane.pb``
(encoded by ``test_moe_reader.py``'s helpers, with hand-worked sums) through
the one shared read of the capture, the probe's two gauges, and their
reading of what the builder's own traced run of the cell recorded (my chip
run, PR 58): the capture's scoped ops, equal ones summed, cut by
``benchmark/layers/kda.py``'s command, with that run's result line beside
them (``traced_line``). No JAX."""

import gzip
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
from bench_tiny import REPO  # noqa: E402,F401
from test_moe_reader import MS, _capture, _plane  # noqa: E402

from benchmark.layers import eshare, kda, mswa, swa  # noqa: E402
from benchmark.lib import cell as cell_lib  # noqa: E402
from benchmark.lib import trace_reduce as tr  # noqa: E402

V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
DATA = os.path.join(HERE, "data")
CELL = "mellum2-12b-a2.5b.collective-swa-moe.1chip"
CFG = cell_lib.load_json(os.path.join(
    REPO, "benchmark", "configs", "mellum2-12b-a2.5b.json"))
ROWS = CFG["batch_per_chip"]


def test_the_two_attention_rooflines_by_hand():
    """``layers/mswa.py``'s docstring: three windowed layers over the band's
    pairs, a global one over the causal triangle's, 32 heads in both, the
    cell's two sequences; both bound by arithmetic."""
    s = CFG["seq_len"]
    assert (s, ROWS, CFG["num_hidden_layers"], CFG["sliding_window"],
            CFG["num_attention_heads"], CFG["num_key_value_heads"]) == (
        8_192, 2, 4, 1024, 32, 4)
    band, triangle = 1024 * s - 523_776, s * (s + 1) // 2
    assert (swa.needed_pairs(s, 1024), swa.needed_pairs(s)) == (
        band, triangle) == (7_864_832, 33_558_528)
    window_flops = 3 * swa.attend_flops(ROWS, s, 32, 128, 1024)
    full_flops = swa.attend_flops(ROWS, s, 32, 128)
    assert window_flops == 2 * 3 * 32 * 1536 * band == 2_319_433_334_784
    assert full_flops == 2 * 32 * 1536 * triangle == 3_298_937_536_512
    layer_bytes = swa.attend_bytes(ROWS, s, 32, 4, 128)
    assert layer_bytes == 2 * 2 * 2 * 8_192 * (2 * 32 + 2 * 4) * 128 \
        == 603_979_776
    window_ms, full_ms = 1e3 * window_flops / 197e12, 1e3 * full_flops / 197e12
    assert window_ms == pytest.approx(11.774, abs=1e-3)
    assert full_ms == pytest.approx(16.746, abs=1e-3)
    assert 1e3 * 3 * layer_bytes / 819e9 < window_ms    # arithmetic binds
    assert 1e3 * layer_bytes / 819e9 < full_ms
    assert mswa.roofline_pct(40.0, CFG, ROWS, V5E, True) == pytest.approx(
        100 * window_ms / 40.0)
    assert mswa.roofline_pct(40.0, CFG, ROWS, V5E, False) == pytest.approx(
        100 * full_ms / 40.0)
    assert mswa.roofline_pct(full_ms, CFG, ROWS, V5E, False) == \
        pytest.approx(100.0)
    # the deployment's four rows: twice the cell's work
    assert mswa.roofline_pct(40.0, CFG, 4, V5E, True) == pytest.approx(
        2 * 100 * window_ms / 40.0)


def test_the_grouped_matmuls_roofline_by_hand():
    """``eshare.gmm_roofline_pct`` at this cell's shapes: at even routing
    2 x 8,192 x 8 x 16 / 64 = 32,768 rows reach the held experts in each of
    four layers; nine calls, the 16 held experts' weights only."""
    rows = 4 * 2 * 8_192 * 8 * 16 // 64
    assert rows == 131_072
    flops = eshare.gmm_flops(rows, 2304, 896)
    assert flops == 9 * 2 * rows * 2304 * 896 == 4_870_492_913_664
    moved = eshare.gmm_bytes(rows, 16, 2304, 896, 4)
    assert moved == 9 * 2 * (rows * 3200 + 4 * 16 * 2304 * 896) \
        == 9_927_917_568
    flops_ms, bytes_ms = 1e3 * flops / 197e12, 1e3 * moved / 819e9
    assert flops_ms == pytest.approx(24.723, abs=1e-3)
    assert bytes_ms == pytest.approx(12.122, abs=1e-3)   # arithmetic binds
    assert eshare.gmm_roofline_pct(80.0, CFG, rows, V5E, 4) == pytest.approx(
        100 * flops_ms / 80.0)


FWD = "jit(_step)/jvp(MellumModel)/layer_%d/"
BACK = "jit(_step)/transpose(jvp(MellumModel))/layer_%d/"
OPS = [
    ("%bps_flash_fwd.7 = (bf16[64,8192,128]) custom-call(...)",
     FWD % 1 + "mixer/attn/bps.swa.window/bps.attn.kernel/pallas_call:",
     "str", [2 * MS] * 2),
    ("%bps_flash_bwd.2 = (bf16[64,8192,128]) custom-call(...)",
     BACK % 2 + "checkpoint/mixer/attn/bps.swa.window/bps.attn.kernel/"
     "jit(_flash_bwd_impl)/pallas_call:", "ref", [6 * MS] * 2),
    ("%bps_flash_fwd.8 = (bf16[64,8192,128]) custom-call(...)",
     FWD % 3 + "mixer/attn/bps.swa.full/bps.attn.kernel/pallas_call:",
     "str", [10 * MS] * 2),
    ("%bps_flash_bwd.1 = (bf16[64,8192,128]) custom-call(...)",
     BACK % 3 + "checkpoint/mixer/attn/bps.swa.full/bps.attn.kernel/"
     "jit(_flash_bwd_impl)/pallas_call:", "ref", [15 * MS] * 2),
    ("%fusion.7 = bf16[16384,4096]{1,0} fusion(...)",
     FWD % 1 + "mixer/attn/bps.swa.proj/q/dot_general:", "str",
     [4 * MS] * 2),
    ("%fusion.8 = f32[2,8192,32,128]{3,2,1,0} fusion(...)",
     BACK % 3 + "checkpoint/mixer/attn/bps.swa.proj/mul:", "str",
     [MS] * 2),
    ("%fusion.6 = bf16[65536,2304]{1,0} fusion(...)",
     FWD % 2 + "ffn/moe/bps.moe.route/gather:", "str", [4 * MS] * 2),
    ("%ragged-dot-metadata = (s32[17]{0}) custom-call(...)",
     "ragged-dot-metadata:", "str", [MS // 4] * 2),
    ("%ragged-dot-none.7 = bf16[65536,896]{1,0} custom-call(...)",
     BACK % 3 + "checkpoint/ffn/moe/bps.moe.experts/ragged_dot:", "str",
     [8 * MS] * 2),
    ("%fusion.10 = bf16[65536,896]{1,0} fusion(...)",
     FWD % 3 + "ffn/moe/bps.moe.experts/mul:", "str", [2 * MS] * 2),
    # the head's scan, as the chip writes it: a container, counted nowhere
    ("%while.4 = (s32[], f32[8,2048]) while(...)",
     "jit(_step)/jvp(MellumModel)/while:", "str", [9 * MS] * 2),
    ("%fusion.54 = f32[24576,2304]{1,0} fusion(...)",
     "jit(_step)/adamw/mul:", "str", [4 * MS] * 2),
]


def _run(tmp_path, trace, **more):
    return types.SimpleNamespace(
        trace={"steps": 2}, out_dir=str(tmp_path), layout=tr.TPU,
        probes={}, config=types.SimpleNamespace(), cfg=dict(CFG),
        rows=ROWS, chips=1, **more)


def test_each_kind_of_layer_counts_under_its_own_scope(tmp_path,
                                                       monkeypatch):
    """Two steps; the programs' line reads 0.999 ms over them. Windowed 2 +
    6, global 10 + 15, projections 4 + 1; route 4 + 0.25 (the metadata
    helper), the grouped matmul 8, the rest of the experts' scope 2. The
    ratio comes from the counters, the load, the rows and the layers from
    the probe."""
    trace = _capture(tmp_path, [_plane("/device:TPU:0", OPS)])
    monkeypatch.setattr("jax.devices", lambda: [types.SimpleNamespace(
        device_kind="TPU v5 lite")])
    monkeypatch.setattr(swa, "walked_pairs_ratio", lambda: 1.5)
    run = _run(tmp_path, trace)
    run.probes.update(bps_moe_held_load=0.97, eshare_held_rows=131_072,
                      eshare_expert_layers=4)
    got = {**mswa.read(run), **eshare.read(run)}
    programs_ms = 999_000_000 * 1e-9 / 2               # 0.4995 ms a step
    assert got == {
        "mswa.window_ms": 8.0, "mswa.full_ms": 25.0, "mswa.proj_ms": 5.0,
        "mswa.layer_share_pct": pytest.approx(100 * 38.0 / programs_ms),
        "mswa.window_roofline_pct": pytest.approx(100 * 11.774 / 8.0,
                                                  abs=1e-2),
        "mswa.full_roofline_pct": pytest.approx(100 * 16.746 / 25.0,
                                                abs=1e-2),
        "mswa.walked_pairs_ratio": 1.5,
        "eshare.route_ms": 4.25, "eshare.gmm_ms": 8.0,
        "eshare.layer_share_pct": pytest.approx(100 * 14.25 / programs_ms),
        "eshare.gmm_roofline_pct": pytest.approx(100 * 24.723 / 8.0,
                                                 abs=1e-2),
        "eshare.held_load": 0.97}


def test_a_capture_without_the_scopes_reports_nothing(tmp_path, monkeypatch):
    """As the parent's program is: no scope, no counter, no probe; the
    readers return nothing that has a value and do not raise, traced or
    not."""
    from byteps_tpu.monitor import metrics

    monkeypatch.setattr(metrics, "counter", lambda name: 0.0)
    trace = _capture(tmp_path, [_plane("/device:TPU:0", OPS[-1:])])
    run = _run(tmp_path, trace)
    assert mswa.read(run) == {"mswa.walked_pairs_ratio": None}
    assert eshare.read(run) == {"eshare.held_load": None}
    run.trace = None
    assert mswa.read(run) == {"mswa.walked_pairs_ratio": None}
    assert eshare.read(run) == {"eshare.held_load": None}
    eshare.setup(run)                    # no probe to run: nothing, no raise
    run.config = types.SimpleNamespace(layer_stats=None, FIRST={})
    eshare.setup(run)
    assert run.probes == {}


@pytest.mark.parametrize("reader,prefix,layer", [
    (mswa, "mswa.", "windowed and global attention")])
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


def test_the_reader_is_the_older_one_under_this_cell_s_names():
    assert {name.partition(".")[2]: m for name, m in mswa.METRICS.items()} \
        == {name.partition(".")[2]: m for name, m in swa.METRICS.items()}
    assert mswa.LAYER == swa.LAYER


def test_the_scopes_and_counters_are_the_program_s():
    """Read, not imported: no JAX here."""
    def source(*path):
        with open(os.path.join(REPO, "byteps_tpu", *path)) as f:
            return f.read()

    model, laguna, experts = (source("models", "mellum.py"),
                              source("models", "laguna.py"),
                              source("parallel", "moe.py"))
    for scope in ("WINDOW_SCOPE", "FULL_SCOPE", "PROJ_SCOPE"):
        assert scope in model       # imported from Laguna's, not renamed
    assert 'WINDOW_SCOPE = "%s"' % swa.SCOPES["window"] in laguna
    assert 'FULL_SCOPE = "%s"' % swa.SCOPES["full"] in laguna
    assert 'PROJ_SCOPE = "%s"' % swa.SCOPES["proj"] in laguna
    assert '"bps_moe_compact_share"' in experts
    assert '"bps_moe_held_load"' in experts
    assert '"%s"' % mswa.WINDOWED in laguna


# --------------------------------------------------------------------------
# What the builder's traced run recorded.

RECORDED = os.path.join(DATA, "collective-swa-moe-1chip.scoped-ops.json.gz")


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as f:
        return json.load(f)


def test_the_readers_over_the_recorded_scoped_ops(recorded):
    """The capture's ops under their scopes, as the chip wrote them: the
    readers' sums are the traced line's, and every share and all three
    rooflines are under 100%."""
    steps = recorded["steps"]
    ops = [(name, tf_op, ps) for name, tf_op, ps, _ in recorded["ops"]]
    programs_ms = recorded["programs_ps"] * 1e-9 / steps
    want = recorded["traced_line"]
    got = kda.scoped_ms(ops, swa.SCOPES, steps)
    for key in ("window", "full", "proj"):
        assert got[key] == pytest.approx(want[f"mswa.{key}_ms"], rel=1e-9)
    assert 100 * sum(got.values()) / programs_ms == pytest.approx(
        want["mswa.layer_share_pct"], rel=1e-9)
    for key, windowed in (("window", True), ("full", False)):
        assert mswa.roofline_pct(got[key], CFG, ROWS, V5E, windowed) == \
            pytest.approx(want[f"mswa.{key}_roofline_pct"], rel=1e-9)
        assert 0 < want[f"mswa.{key}_roofline_pct"] < 100
    # the expert layers beside them (``test_eshare_reader.py`` holds their
    # figures over this list): the two layers together inside the step
    share = eshare.split_ms(ops, steps)
    assert want["mswa.layer_share_pct"] \
        + 100 * sum(share.values()) / programs_ms < 100


def test_the_kernels_in_the_recorded_capture(recorded):
    """Every flash kernel of the step lies under one of the two kinds'
    scopes and inside ``bps.attn.kernel``: three windowed layers and a
    global one at 2 x 32 head-rows, the forward twice a layer (each mixer
    half is recomputed) and the one fused backward kernel once."""
    calls = {}
    for name, tf_op, _, count in recorded["ops"]:
        if "bps_flash" not in name:
            continue
        assert "bps.attn.kernel" in tf_op
        kind = [k for k in ("window", "full") if swa.SCOPES[k] in tf_op]
        assert len(kind) == 1, tf_op
        kernel = name.split("=")[0].strip("% ").split(".")[0]
        calls[kernel, kind[0]] = calls.get(
            (kernel, kind[0]), 0) + count // recorded["steps"]
    assert calls == {
        ("bps_flash_fwd", "window"): 6, ("bps_flash_fwd", "full"): 2,
        ("bps_flash_bwd", "window"): 3, ("bps_flash_bwd", "full"): 1}

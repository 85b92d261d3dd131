"""The three readers of ``qwen3-next-80b-a3b.collective-gdn.1chip``
(``benchmark/layers/gdn.py``, ``gattn.py``, ``eshare.py``): the rooflines'
operations and bytes by hand at the cell's size, their reading of a made-up
``.xplane.pb`` (encoded by ``test_moe_reader.py``'s helpers, with hand-worked
sums) through the one shared read of the capture, and their reading of what
the builder's own traced run of the cell recorded (my chip run, PR 50): the
capture's scoped ops, equal ones summed, cut by ``benchmark/layers/kda.py``'s
command, with that run's result line beside them (``traced_line``). No
JAX."""

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

from benchmark.layers import eshare, gattn, gdn, kda, swa  # noqa: E402
from benchmark.lib import cell as cell_lib  # noqa: E402
from benchmark.lib import trace_reduce as tr  # noqa: E402

V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
DATA = os.path.join(HERE, "data")
CELL = "qwen3-next-80b-a3b.collective-gdn.1chip"
CFG = cell_lib.load_json(os.path.join(
    REPO, "benchmark", "configs", "qwen3-next-80b-a3b.json"))


def test_the_scan_s_roofline_by_hand():
    """``layers/gdn.py``'s docstring: the recurrence token by token over 32
    value heads and three layers, q and k read at the 16 key heads, one
    float of decay a head and token; bound by bandwidth. No chunk length in
    either count."""
    s = CFG["seq_len"]
    assert (s, CFG["num_hidden_layers"], gdn.linear_layers(CFG)) == (
        16_384, 4, 3)
    assert gdn.linear_layers({**CFG, "num_hidden_layers": 48}) == 36
    flops = gdn.scan_flops(s, 32, 128, 128, 3)
    assert flops == 3 * 3 * s * 32 * 114_688 == 541_165_879_296
    nbytes = gdn.scan_bytes(s, 16, 32, 128, 128, 3)
    assert nbytes == 3 * 2 * s * 4 * (2 * 2048 + 2 * 4096 + 64) \
        == 4_857_004_032
    flops_ms, bytes_ms = 1e3 * flops / 197e12, 1e3 * nbytes / 819e9
    assert flops_ms == pytest.approx(2.7470, abs=1e-3)
    assert bytes_ms == pytest.approx(5.9304, abs=1e-3)      # bandwidth binds
    assert gdn.scan_roofline_pct(60.0, CFG, s, V5E) == pytest.approx(
        100 * bytes_ms / 60.0)
    assert gdn.scan_roofline_pct(bytes_ms, CFG, s, V5E) == \
        pytest.approx(100.0)
    # another chunk length: the same count
    assert gdn.scan_roofline_pct(60.0, {**CFG, "gdn_chunk": 128}, s, V5E) \
        == gdn.scan_roofline_pct(60.0, CFG, s, V5E)
    # with a decay a channel and as many key heads the bytes would be KDA's
    assert kda.scan_bytes(s, 32, 128, 128, 32, 1) > gdn.scan_bytes(
        s, 16, 32, 128, 128, 1)


def test_the_attention_s_roofline_by_hand():
    """``layers/gattn.py``'s docstring: one layer of 16 heads 256 wide over
    the causal triangle; bound by arithmetic."""
    s = CFG["seq_len"]
    triangle = s * (s + 1) // 2
    flops = swa.attend_flops(1, s, 16, 256)
    assert flops == 16 * 3072 * triangle == 6_597_472_419_840
    nbytes = swa.attend_bytes(1, s, 16, 2, 256)
    assert nbytes == 2 * 2 * s * (2 * 16 + 2 * 2) * 256 == 603_979_776
    flops_ms = 1e3 * flops / 197e12
    assert flops_ms == pytest.approx(33.4897, abs=1e-3)
    assert 1e3 * nbytes / 819e9 < flops_ms                 # arithmetic binds
    assert gattn.attend_roofline_pct(30.0, CFG, 1, V5E) == pytest.approx(
        100 * flops_ms / 30.0)
    # two periods: two such layers
    assert gattn.attend_roofline_pct(
        30.0, {**CFG, "num_hidden_layers": 8}, 1, V5E) == pytest.approx(
        2 * 100 * flops_ms / 30.0)


def test_the_grouped_matmuls_roofline_by_hand():
    """``eshare.gmm_roofline_pct`` at this cell's shapes: the rows
    that reached the 32 held experts, nine calls, the held weights only. At
    even routing 4 x 10,240 rows: bound by the weights' bytes."""
    rows = 4 * 10_240
    flops = eshare.gmm_flops(rows, 2048, 512)
    assert flops == 9 * 2 * rows * 2048 * 512 == 773_094_113_280
    nbytes = eshare.gmm_bytes(rows, 32, 2048, 512, 4)
    assert nbytes == 9 * 2 * (rows * 2560 + 4 * 32 * 2048 * 512) \
        == 4_303_355_904
    assert 1e3 * nbytes / 819e9 > 1e3 * flops / 197e12      # bandwidth binds
    assert eshare.gmm_roofline_pct(20.0, CFG, rows, V5E, 4) == pytest.approx(
        100 * (1e3 * nbytes / 819e9) / 20.0)


FWD = "jit(_step)/jvp(Qwen3NextModel)/layer_%d/"
BACK = "jit(_step)/transpose(jvp(Qwen3NextModel))/layer_%d/"
OPS = [
    ("%fusion.1 = f32[1,8192,8192]{2,1,0} fusion(...)",
     FWD % 0 + "mixer/gdn/checkpoint/bps.gdn.prep/mul:", "str",
     [2 * MS] * 2),
    ("%fusion.2 = f32[1,256,32,32]{3,2,1,0} fusion(...)",
     BACK % 1 + "checkpoint/mixer/gdn/bps.gdn.prep/cumsum:", "str",
     [MS] * 2),
    ("%fusion.3 = bf16[4,1,32,32,128]{4,3,2,1,0} fusion(...)",
     FWD % 1 + "mixer/gdn/bps.gdn.scan/while/body/checkpoint/dot_general:",
     "str", [6 * MS] * 2),
    ("%fusion.4 = f32[1,32,128,128]{3,2,1,0} fusion(...)",
     BACK % 2 + "checkpoint/mixer/gdn/bps.gdn.scan/while/body/mul:", "ref",
     [14 * MS] * 2),
    # the scan's container: as long as its body, counted nowhere
    ("%while.7 = (s32[], f32[1,32,128,128]) while(...)",
     FWD % 1 + "mixer/gdn/bps.gdn.scan/while:", "str", [6 * MS] * 2),
    ("%fusion.5 = f32[1,8192,4096]{2,1,0} fusion(...)",
     FWD % 2 + "mixer/gdn/bps.gdn.out/mul:", "str", [3 * MS] * 2),
    ("%bps_flash_fwd.3 = (bf16[16,8192,256]) custom-call(...)",
     FWD % 3 + "mixer/attn/bps.gattn.attend/bps.attn.kernel/pallas_call:",
     "str", [5 * MS] * 2),
    ("%bps_flash_dkv.1 = (bf16[2,8192,256]) custom-call(...)",
     BACK % 3 + "checkpoint/mixer/attn/bps.gattn.attend/bps.attn.kernel/"
     "jit(_flash_bwd_impl)/pallas_call:", "ref", [15 * MS] * 2),
    ("%fusion.7 = bf16[8192,8192]{1,0} fusion(...)",
     FWD % 3 + "mixer/attn/bps.gattn.proj/q/dot_general:", "str",
     [4 * MS] * 2),
    ("%fusion.8 = f32[1,8192,16,256]{3,2,1,0} fusion(...)",
     BACK % 3 + "checkpoint/mixer/attn/bps.gattn.proj/mul:", "str",
     [MS] * 2),
    ("%fusion.6 = bf16[10240,2048]{1,0} fusion(...)",
     FWD % 2 + "ffn/moe/bps.moe.route/gather:", "str", [4 * MS] * 2),
    ("%ragged-dot-metadata = (s32[33]{0}) custom-call(...)",
     "ragged-dot-metadata:", "str", [MS // 4] * 2),
    ("%ragged-dot-none.7 = bf16[10240,512]{1,0} custom-call(...)",
     BACK % 3 + "checkpoint/ffn/moe/bps.moe.experts/ragged_dot:", "str",
     [MS] * 2),
    ("%fusion.9 = bf16[32,2048,512]{2,1,0} fusion(...)",
     FWD % 0 + "ffn/moe/bps.moe.experts/convert_element_type:", "str",
     [MS // 2] * 2),
    ("%fusion.10 = bf16[8192,512]{1,0} fusion(...)",
     FWD % 3 + "ffn/moe/bps.moe.shared/shared/up/dot_general:", "str",
     [2 * MS] * 2),
    ("%fusion.54 = f32[18992,2048]{1,0} fusion(...)",
     "jit(_step)/adamw/mul:", "str", [4 * MS] * 2),
]


def _run(tmp_path, trace, **more):
    return types.SimpleNamespace(
        trace={"steps": 2}, out_dir=str(tmp_path), layout=tr.TPU,
        probes={}, config=types.SimpleNamespace(), cfg=dict(CFG),
        rows=1, chips=1, **more)


def test_each_layer_counts_under_its_own_scope(tmp_path, monkeypatch):
    """Two steps; the programs' line reads 0.999 ms over them. Scan 6 + 14
    (its container skipped), prep 2 + 1, out 3; attend 5 + 15, proj 4 + 1;
    grouped matmuls 1, route 4 + 0.25 (the metadata helper), the experts'
    casts 0.5, shared 2."""
    trace = _capture(tmp_path, [_plane("/device:TPU:0", OPS)])
    monkeypatch.setattr("jax.devices", lambda: [types.SimpleNamespace(
        device_kind="TPU v5 lite")])
    run = _run(tmp_path, trace)
    run.probes.update(bps_moe_held_load=0.9, eshare_held_rows=40_960,
                      eshare_expert_layers=4)
    got = {**gdn.read(run), **gattn.read(run), **eshare.read(run)}
    programs_ms = 999_000_000 * 1e-9 / 2               # 0.4995 ms a step
    assert got == {
        "gdn.scan_ms": 20.0, "gdn.prep_ms": 3.0,
        "gdn.layer_share_pct": pytest.approx(100 * 26.0 / programs_ms),
        "gdn.scan_roofline_pct": pytest.approx(100 * 5.9304 / 20.0,
                                               abs=1e-2),
        "gattn.attend_ms": 20.0, "gattn.proj_ms": 5.0,
        "gattn.attend_roofline_pct": pytest.approx(100 * 33.4897 / 20.0,
                                                   abs=1e-2),
        "eshare.gmm_ms": 1.0, "eshare.route_ms": 4.25,
        "eshare.gmm_roofline_pct": pytest.approx(
            100 * (1e3 * 4_303_355_904 / 819e9) / 1.0, abs=1e-2),
        "eshare.layer_share_pct": pytest.approx(100 * 7.75 / programs_ms),
        "eshare.held_load": 0.9}
    assert run.probes["gdn_out_ms"] == 3.0
    assert run.probes["eshare_shared_ms"] == 2.0


def test_a_capture_without_the_scopes_reports_nothing(tmp_path):
    """As the parent's program is: no scope, no kernel, no collection; the
    readers return nothing that has a value and do not raise, traced or
    not."""
    trace = _capture(tmp_path, [_plane("/device:TPU:0", OPS[-1:])])
    run = _run(tmp_path, trace)
    assert gdn.read(run) == {} and gattn.read(run) == {}
    assert eshare.read(run) == {"eshare.held_load": None}
    run.trace = None
    assert gdn.read(run) == {} and gattn.read(run) == {}
    assert eshare.read(run) == {"eshare.held_load": None}
    gdn.setup(run)                      # no probe to run: nothing, no raise
    eshare.setup(run)
    run.config = types.SimpleNamespace(layer_stats=lambda cfg, rows: {},
                                       FIRST={})
    gdn.setup(run)
    eshare.setup(run)
    assert run.probes == {}


@pytest.mark.parametrize("reader,prefix,layer", [
    (gdn, "gdn.", "per-head linear attention"),
    (gattn, "gattn.", "gated attention")])
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


def test_the_scopes_are_the_program_s():
    """Read, not imported: no JAX here."""
    def source(*path):
        with open(os.path.join(REPO, "byteps_tpu", *path)) as f:
            return f.read()

    model, scan = (source("models", "qwen3_next.py"),
                   source("parallel", "linear_attention.py"))
    assert ('GDN_PREP_SCOPE, GDN_SCAN_SCOPE = "%s", "%s"'
            % (gdn.SCOPES["prep"], gdn.SCOPES["scan"])) in scan
    assert 'GDN_OUT_SCOPE = "%s"' % gdn.SCOPES["out"] in model
    assert 'GATTN_ATTEND_SCOPE = "%s"' % gattn.SCOPES["attend"] in model
    assert 'GATTN_PROJ_SCOPE = "%s"' % gattn.SCOPES["proj"] in model
    assert '"%s"' % gdn.LINEAR in model


# --------------------------------------------------------------------------
# What the builder's traced run recorded.

RECORDED = os.path.join(DATA, "collective-gdn-1chip.scoped-ops.json.gz")


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as f:
        return json.load(f)


def test_the_readers_over_the_recorded_scoped_ops(recorded):
    """The capture's ops under their scopes, as the chip wrote them: the
    readers' sums are the traced line's, and every share and all three
    rooflines are between 0 and 100%."""
    steps = recorded["steps"]
    ops = [(name, tf_op, ps) for name, tf_op, ps, _ in recorded["ops"]]
    programs_ms = recorded["programs_ps"] * 1e-9 / steps
    want = recorded["traced_line"]
    got = kda.scoped_ms(ops, gdn.SCOPES, steps)
    assert got["scan"] == pytest.approx(want["gdn.scan_ms"], rel=1e-9)
    assert got["prep"] == pytest.approx(want["gdn.prep_ms"], rel=1e-9)
    assert 100 * sum(got.values()) / programs_ms == pytest.approx(
        want["gdn.layer_share_pct"], rel=1e-9)
    assert gdn.scan_roofline_pct(got["scan"], CFG, CFG["seq_len"], V5E) \
        == pytest.approx(want["gdn.scan_roofline_pct"], rel=1e-9)
    attention = kda.scoped_ms(ops, gattn.SCOPES, steps)
    assert attention["attend"] == pytest.approx(want["gattn.attend_ms"],
                                                rel=1e-9)
    assert attention["proj"] == pytest.approx(want["gattn.proj_ms"],
                                              rel=1e-9)
    assert gattn.attend_roofline_pct(attention["attend"], CFG, 1, V5E) \
        == pytest.approx(want["gattn.attend_roofline_pct"], rel=1e-9)
    for name in ("gdn.scan_roofline_pct", "gattn.attend_roofline_pct",
                 "gdn.layer_share_pct"):
        assert 0 < want[name] < 100, name
    # the expert layers beside them: ``test_eshare_reader.py`` holds their
    # figures over this list
    assert sum(eshare.split_ms(ops, steps).values()) + sum(
        got.values()) + sum(attention.values()) < programs_ms


def test_the_kernels_in_the_recorded_capture(recorded):
    """The gated attention layer's flash kernels lie under its scope and
    inside ``bps.attn.kernel``: the forward twice (the mixer half is
    recomputed) at the 16 query heads, dQ at the query heads and dK/dV at
    the 2 key heads (the group summed inside the kernel), all 256 wide; no
    op of the step lies under Kimi-Linear's scan scopes."""
    calls = {}
    for name, tf_op, _, count in recorded["ops"]:
        assert "bps.kda." not in tf_op
        if "bps_flash" not in name:
            continue
        assert "bps.attn.kernel" in tf_op and gattn.SCOPES["attend"] in tf_op
        kernel = name.split("=")[0].strip("% ").split(".")[0]
        shape = name.split("bf16[")[1].split("]")[0].split(",")
        assert shape[-1] == "256"
        calls[kernel, int(shape[0])] = calls.get(
            (kernel, int(shape[0])), 0) + count // recorded["steps"]
    assert calls == {("bps_flash_fwd", 16): 2, ("bps_flash_dq", 16): 1,
                     ("bps_flash_dkv", 2): 1}

"""The two readers of ``laguna-xs.2.collective-swa.1chip``
(``benchmark/layers/swa.py``, ``eshare.py``): the rooflines' operations and
bytes by hand at the cell's size, their reading of a made-up ``.xplane.pb``
(encoded by ``test_moe_reader.py``'s helpers, with hand-worked sums) through
the one shared read of the capture, the counters' ratio, and their reading
of what the builder's own traced run of the cell recorded (my chip run, PR
47): the capture's scoped ops, equal ones summed, cut by
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

from benchmark.layers import eshare, kda, moe, swa  # noqa: E402
from benchmark.lib import cell as cell_lib  # noqa: E402
from benchmark.lib import trace_reduce as tr  # noqa: E402

V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
DATA = os.path.join(HERE, "data")
CELL = "laguna-xs.2.collective-swa.1chip"
CFG = cell_lib.load_json(os.path.join(
    REPO, "benchmark", "configs", "laguna-xs.2.json"))


def test_the_two_rooflines_by_hand():
    """``layers/swa.py``'s docstring: three windowed layers of 64 heads over
    the band's pairs, two global ones of 48 over the causal triangle's; both
    bound by arithmetic."""
    s = CFG["seq_len"]
    assert (s, CFG["num_hidden_layers"], CFG["sliding_window"]) == (
        8_192, 5, 512)
    band, triangle = 512 * s - 130_816, s * (s + 1) // 2
    assert (swa.needed_pairs(s, 512), swa.needed_pairs(s)) == (
        band, triangle) == (4_063_488, 33_558_528)
    assert swa.needed_pairs(256, 512) == 256 * 257 // 2
    window_flops = 3 * swa.attend_flops(1, s, 64, 128, 512)
    full_flops = 2 * swa.attend_flops(1, s, 48, 128)
    assert window_flops == 3 * 64 * 1536 * band == 1_198_371_373_056
    assert full_flops == 2 * 48 * 1536 * triangle == 4_948_406_304_768
    window_bytes = 3 * swa.attend_bytes(1, s, 64, 8, 128)
    full_bytes = 2 * swa.attend_bytes(1, s, 48, 8, 128)
    assert window_bytes == 3 * 2 * 2 * 8_192 * (2 * 64 + 16) * 128 \
        == 1_811_939_328
    assert full_bytes == 2 * 2 * 2 * 8_192 * (2 * 48 + 16) * 128 \
        == 939_524_096
    window_ms, full_ms = 1e3 * window_flops / 197e12, 1e3 * full_flops / 197e12
    assert window_ms == pytest.approx(6.083, abs=1e-3)
    assert full_ms == pytest.approx(25.119, abs=1e-3)
    assert 1e3 * window_bytes / 819e9 < window_ms     # arithmetic binds
    assert 1e3 * full_bytes / 819e9 < full_ms
    assert swa.roofline_pct(20.0, CFG, 1, V5E, True) == pytest.approx(
        100 * window_ms / 20.0)
    assert swa.roofline_pct(50.0, CFG, 1, V5E, False) == pytest.approx(
        100 * full_ms / 50.0)
    assert swa.roofline_pct(window_ms, CFG, 1, V5E, True) == \
        pytest.approx(100.0)
    # two rows a chip: twice the work
    assert swa.roofline_pct(50.0, CFG, 2, V5E, False) == pytest.approx(
        2 * 100 * full_ms / 50.0)


FWD = "jit(_step)/jvp(LagunaModel)/layer_%d/"
BACK = "jit(_step)/transpose(jvp(LagunaModel))/layer_%d/"
OPS = [
    ("%bps_flash_fwd.7 = (bf16[64,8192,128]) custom-call(...)",
     FWD % 1 + "mixer/attn/bps.swa.window/bps.attn.kernel/pallas_call:",
     "str", [2 * MS] * 2),
    ("%bps_flash_dkv.2 = (bf16[8,8192,128]) custom-call(...)",
     BACK % 2 + "checkpoint/mixer/attn/bps.swa.window/bps.attn.kernel/"
     "jit(_flash_bwd_impl)/pallas_call:", "ref", [3 * MS] * 2),
    ("%bps_flash_fwd.8 = (bf16[48,8192,128]) custom-call(...)",
     FWD % 0 + "mixer/attn/bps.swa.full/bps.attn.kernel/pallas_call:",
     "str", [10 * MS] * 2),
    ("%bps_flash_dq.1 = (bf16[48,8192,128]) custom-call(...)",
     BACK % 4 + "checkpoint/mixer/attn/bps.swa.full/bps.attn.kernel/"
     "jit(_flash_bwd_impl)/pallas_call:", "ref", [15 * MS] * 2),
    ("%fusion.7 = bf16[8192,8192]{1,0} fusion(...)",
     FWD % 1 + "mixer/attn/bps.swa.proj/q/dot_general:", "str",
     [4 * MS] * 2),
    ("%fusion.8 = f32[1,8192,48,128]{3,2,1,0} fusion(...)",
     BACK % 4 + "checkpoint/mixer/attn/bps.swa.proj/mul:", "str",
     [MS] * 2),
    ("%fusion.6 = bf16[65536,2048]{1,0} fusion(...)",
     FWD % 2 + "ffn/moe/bps.moe.route/gather:", "str", [4 * MS] * 2),
    ("%ragged-dot-metadata = (s32[17]{0}) custom-call(...)",
     "ragged-dot-metadata:", "str", [MS // 4] * 2),
    ("%ragged-dot-none.7 = bf16[8192,512]{1,0} custom-call(...)",
     BACK % 3 + "checkpoint/ffn/moe/bps.moe.experts/ragged_dot:", "str",
     [MS] * 2),
    ("%fusion.10 = bf16[8192,512]{1,0} fusion(...)",
     FWD % 3 + "ffn/moe/bps.moe.shared/shared/up/dot_general:", "str",
     [2 * MS] * 2),
    # the head's scan, as the chip writes it: a container, counted nowhere
    ("%while.4 = (s32[], f32[4,2048]) while(...)",
     "jit(_step)/jvp(LagunaModel)/while:", "str", [9 * MS] * 2),
    ("%fusion.54 = f32[12544,2048]{1,0} fusion(...)",
     "jit(_step)/adamw/mul:", "str", [4 * MS] * 2),
]


def _run(tmp_path, trace, **more):
    return types.SimpleNamespace(
        trace={"steps": 2}, out_dir=str(tmp_path), layout=tr.TPU,
        probes={}, config=types.SimpleNamespace(), cfg=dict(CFG),
        rows=1, chips=1, **more)


def test_each_kind_of_layer_counts_under_its_own_scope(tmp_path,
                                                       monkeypatch):
    """Two steps; the programs' line reads 0.999 ms over them. Windowed 2 +
    3, global 10 + 15, projections 4 + 1; route 4 + 0.25 (the metadata
    helper), the kernel 1, shared 2. The capture is parsed twice (ops,
    programs) whichever readers ask. The ratio comes from the counters."""
    trace = _capture(tmp_path, [_plane("/device:TPU:0", OPS)])
    reads = []
    monkeypatch.setattr(moe, "scoped_ops", lambda *a, real=moe.scoped_ops:
                        reads.append(a) or real(*a))
    monkeypatch.setattr("jax.devices", lambda: [types.SimpleNamespace(
        device_kind="TPU v5 lite")])
    monkeypatch.setattr(swa, "walked_pairs_ratio", lambda: 2.0)
    run = _run(tmp_path, trace)
    run.probes["bps_moe_held_load"] = 0.9
    got = {**swa.read(run), **eshare.read(run)}
    assert len(reads) == 2
    programs_ms = 999_000_000 * 1e-9 / 2               # 0.4995 ms a step
    assert got == {
        "swa.window_ms": 5.0, "swa.full_ms": 25.0, "swa.proj_ms": 5.0,
        "swa.layer_share_pct": pytest.approx(100 * 35.0 / programs_ms),
        "swa.window_roofline_pct": pytest.approx(100 * 6.083 / 5.0,
                                                 abs=1e-2),
        "swa.full_roofline_pct": pytest.approx(100 * 25.119 / 25.0,
                                               abs=1e-2),
        "swa.walked_pairs_ratio": 2.0,
        "eshare.route_ms": 4.25, "eshare.gmm_ms": 1.0,
        "eshare.layer_share_pct": pytest.approx(100 * 7.25 / programs_ms),
        "eshare.held_load": 0.9}
    assert run.probes["eshare_shared_ms"] == 2.0


def test_a_capture_without_the_scopes_reports_nothing(tmp_path, monkeypatch):
    """As the parent's program is: no scope, no counter; the readers return
    nothing that has a value and do not raise, traced or not."""
    from byteps_tpu.monitor import metrics

    monkeypatch.setattr(metrics, "counter", lambda name: 0.0)
    trace = _capture(tmp_path, [_plane("/device:TPU:0", OPS[-1:])])
    run = _run(tmp_path, trace)
    assert swa.read(run) == {"swa.walked_pairs_ratio": None}
    assert eshare.read(run) == {"eshare.held_load": None}
    run.trace = None
    assert swa.read(run) == {"swa.walked_pairs_ratio": None}
    assert eshare.read(run) == {"eshare.held_load": None}
    eshare.setup(run)                    # no probe to run: nothing, no raise
    run.config = types.SimpleNamespace(layer_stats=None, FIRST={})
    eshare.setup(run)
    assert run.probes == {}


def test_the_ratio_is_the_counters(monkeypatch):
    from byteps_tpu.monitor import metrics

    values = {swa.WALKED: 3 * 64 * 31 * 512 * 512,
              swa.NEEDED: 3 * 64 * 4_063_488}
    monkeypatch.setattr(metrics, "counter", lambda name: values.get(name, 0))
    assert swa.walked_pairs_ratio() == pytest.approx(31 * 262_144 / 4_063_488)
    assert swa.read(types.SimpleNamespace(trace=None)) == {
        "swa.walked_pairs_ratio": pytest.approx(1.99989, abs=1e-4)}


@pytest.mark.parametrize("reader,prefix,layer", [
    (swa, "swa.", "windowed and global attention")])
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

    model, attention = (source("models", "laguna.py"),
                        source("parallel", "ring_attention.py"))
    assert 'WINDOW_SCOPE = "%s"' % swa.SCOPES["window"] in model
    assert 'FULL_SCOPE = "%s"' % swa.SCOPES["full"] in model
    assert 'PROJ_SCOPE = "%s"' % swa.SCOPES["proj"] in model
    assert 'WINDOW_WALKED = "%s"' % swa.WALKED in attention
    assert 'WINDOW_NEEDED = "%s"' % swa.NEEDED in attention
    assert '"%s"' % swa.WINDOWED in model


# --------------------------------------------------------------------------
# What the builder's traced run recorded.

RECORDED = os.path.join(DATA, "collective-swa-1chip.scoped-ops.json.gz")


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as f:
        return json.load(f)


def test_the_readers_over_the_recorded_scoped_ops(recorded):
    """The capture's ops under their scopes, as the chip wrote them: the
    readers' sums are the traced line's, every share and both rooflines are
    under 100%, and the attention layers are two thirds of the step."""
    steps = recorded["steps"]
    ops = [(name, tf_op, ps) for name, tf_op, ps, _ in recorded["ops"]]
    programs_ms = recorded["programs_ps"] * 1e-9 / steps
    want = recorded["traced_line"]
    got = kda.scoped_ms(ops, swa.SCOPES, steps)
    for key in ("window", "full", "proj"):
        assert got[key] == pytest.approx(want[f"swa.{key}_ms"], rel=1e-9)
    assert 100 * sum(got.values()) / programs_ms == pytest.approx(
        want["swa.layer_share_pct"], rel=1e-9)
    assert want["swa.layer_share_pct"] > 50
    for key, windowed in (("window", True), ("full", False)):
        assert swa.roofline_pct(got[key], CFG, 1, V5E, windowed) == \
            pytest.approx(want[f"swa.{key}_roofline_pct"], rel=1e-9)
        assert 0 < want[f"swa.{key}_roofline_pct"] < 100
    # 31 blocks of 512 x 512 a head over the band's 4,063,488 pairs (and
    # init()'s trace of 8 tokens, whose square of 64 holds 36)
    assert want["swa.walked_pairs_ratio"] == pytest.approx(
        31 * 512 * 512 / 4_063_488, rel=1e-5)
    # the expert layers beside them (``test_eshare_reader.py`` holds their
    # figures): a share of the step well under the attention's
    share = eshare.split_ms(ops, steps)
    assert 0 < 100 * sum(share.values()) / programs_ms \
        < want["swa.layer_share_pct"]


def test_the_kernels_in_the_recorded_capture(recorded):
    """Every flash kernel of the step lies under one of the two kinds'
    scopes and inside ``bps.attn.kernel``: three windowed layers of 64 query
    heads and two global ones of 48, the forward twice a layer (each mixer
    half is recomputed), dQ at the query heads and dK/dV at the 8 key heads
    (the group summed inside the kernel)."""
    calls = {}
    for name, tf_op, _, count in recorded["ops"]:
        if "bps_flash" not in name:
            continue
        assert "bps.attn.kernel" in tf_op
        kind = [k for k in ("window", "full") if swa.SCOPES[k] in tf_op]
        assert len(kind) == 1, tf_op
        kernel = name.split("=")[0].strip("% ").split(".")[0]
        heads = int(name.split("bf16[")[1].split(",")[0])
        calls[kernel, kind[0], heads] = calls.get(
            (kernel, kind[0], heads), 0) + count // recorded["steps"]
    assert calls == {
        ("bps_flash_fwd", "window", 64): 6, ("bps_flash_fwd", "full", 48): 4,
        ("bps_flash_dq", "window", 64): 3, ("bps_flash_dq", "full", 48): 2,
        ("bps_flash_dkv", "window", 8): 3, ("bps_flash_dkv", "full", 8): 2}

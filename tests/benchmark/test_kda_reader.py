"""The three readers of ``kimi-linear-48b-a3b.collective-kda.1chip``
(``benchmark/layers/kda.py``, ``mla.py``, ``eshare.py``): their operations and
bytes by hand at the cell's size, their reading of a made-up ``.xplane.pb``
(encoded by ``test_moe_reader.py``'s helpers, with hand-worked sums) through
the one shared read of the capture, and their reading of what the builder's
own traced run of the cell recorded (my chip run, PR 39): the capture's
scoped ops, equal ones summed, cut by ``benchmark/layers/kda.py``'s command,
with that run's result line beside them (``traced_line``). No JAX."""

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

from benchmark.layers import eshare, kda, mla  # noqa: E402
from benchmark.layers import moe  # noqa: E402
from benchmark.lib import cell as cell_lib  # noqa: E402
from benchmark.lib import trace_reduce as tr  # noqa: E402

V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
DATA = os.path.join(HERE, "data")
CELL = "kimi-linear-48b-a3b.collective-kda.1chip"
CFG = cell_lib.load_json(os.path.join(
    REPO, "benchmark", "configs", "kimi-linear-48b-a3b.json"))
# the size the readers' docstrings work out by hand
LONG = {**CFG, "seq_len": 16_384, "kda_chunk": 64}


def test_scan_operations_and_bytes_by_hand():
    """``layers/kda.py``'s docstring: a chunk of 64 tokens of one head."""
    per_chunk = 64 * 64 * (3 * 128 + 2 * 128) + 6 * 64 * 128 * 128
    assert per_chunk == 2_621_440 + 6_291_456 == 8_912_896
    assert per_chunk // 64 == 139_264 > 7 * 128 * 128      # the recurrence's
    assert kda.scan_flops(16_384, 32, 128, 128, 64, 4) \
        == 3 * 4 * 32 * 256 * per_chunk == 876_173_328_384
    per_token = 4 * 32 * (5 * 128 + 1)
    states = 4 * 32 * 128 * 128 * 256
    assert (per_token * 16_384, states) == (1_344_274_432, 536_870_912)
    assert kda.scan_bytes(16_384, 32, 128, 128, 64, 4) \
        == 4 * 2 * (1_344_274_432 + 536_870_912) == 15_049_162_752
    # 4.4 ms of operations against 18.4 ms of bytes: bound by bandwidth
    least_ms = max(1e3 * 876_173_328_384 / 197e12,
                   1e3 * 15_049_162_752 / 819e9)
    assert least_ms == pytest.approx(18.375, abs=1e-3)
    assert kda.scan_roofline_pct(200.0, LONG, 16_384, V5E) == pytest.approx(
        100 * least_ms / 200.0)
    assert kda.scan_roofline_pct(least_ms, LONG, 16_384, V5E) == \
        pytest.approx(100.0)
    # the cell: 8,192 tokens in chunks of 32
    assert (CFG["seq_len"], CFG["kda_chunk"]) == (8_192, 32)
    per_chunk = 32 * 32 * 640 + 6 * 32 * 128 * 128
    assert per_chunk == 3_801_088
    assert kda.scan_flops(8_192, 32, 128, 128, 32, 4) \
        == 3 * 4 * 32 * 256 * per_chunk == 373_662_154_752
    assert kda.scan_bytes(8_192, 32, 128, 128, 32, 4) \
        == 4 * 2 * (672_137_216 + 536_870_912) == 9_672_065_024
    assert kda.scan_roofline_pct(100.0, CFG, 8_192, V5E) == pytest.approx(
        1e3 * 9_672_065_024 / 819e9, abs=1e-6)             # 11.81 ms least
    # a chunk that does not divide the tokens is a whole chunk's work
    assert kda.scan_flops(65, 1, 8, 8, 64, 1) == 2 * kda.scan_flops(
        64, 1, 8, 8, 64, 1)


def test_attend_operations_and_bytes_by_hand():
    """``layers/mla.py``'s docstring; the count is ``flops_per_token``'s
    attention term times the tokens."""
    pairs = 16_384 * 16_385 // 2
    assert pairs == 134_225_920
    assert mla.attend_flops(1, 16_384, 32, 192, 128, 1) \
        == pairs * 32 * 1_920 == 8_246_840_524_800 == 16_384 * 503_347_200
    assert mla.attend_bytes(1, 16_384, 32, 192, 128, 1) \
        == 2 * 16_384 * 32 * 640 * 2 == 1_342_177_280
    least_ms = 1e3 * 8_246_840_524_800 / 197e12        # operations-bound
    assert least_ms == pytest.approx(41.862, abs=1e-3)
    assert 1e3 * 1_342_177_280 / 819e9 < least_ms
    assert mla.attend_roofline_pct(90.0, LONG, 1, V5E) == pytest.approx(
        100 * least_ms / 90.0)
    # the cell: s 8,192, 33,558,528 pairs, 10.47 ms least
    assert mla.attend_flops(1, 8_192, 32, 192, 128, 1) \
        == 33_558_528 * 32 * 1_920 == 2_061_835_960_320
    assert mla.attend_roofline_pct(10.0, CFG, 1, V5E) == pytest.approx(
        1e5 * 2_061_835_960_320 / 197e12 / 10.0)


KDA = "jit(_step)/jvp(KimiLinearModel)/layer_1/mixer/checkpoint/kda/"
BACK = ("jit(_step)/transpose(jvp(KimiLinearModel))/layer_1/mixer/"
        "rematted_computation/kda/")
OPS = [
    ("%fusion.1 = f32[1,16,32,64,128]{4,3,2,1,0} fusion(...)",
     KDA + "bps.kda.scan/while/body/checkpoint/exp:", "str", [5 * MS] * 2),
    ("%fusion.2 = f32[1,32,128,128]{3,2,1,0} fusion(...)",
     BACK + "bps.kda.scan/while/body/while/body/dot_general:", "ref",
     [3 * MS] * 2),
    ("%fusion.3 = f32[1,16384,4096]{2,1,0} fusion(...)",
     KDA + "checkpoint/bps.kda.prep/logistic:", "str", [2 * MS] * 2),
    ("%fusion.4 = f32[16384,32,128]{2,1,0} fusion(...)",
     BACK + "bps.kda.out/o_norm/mul:", "str", [MS // 2] * 2),
    ("%bps_flash_dkv.1 = (bf16[32,16384,192]) custom-call(...)",
     "jit(_step)/transpose(jvp(KimiLinearModel))/layer_3/mixer/mla/"
     "bps.mla.attend/bps.attn.kernel/jit(_flash_bwd_impl)/pallas_call:",
     "str", [7 * MS] * 2),
    ("%fusion.6 = bf16[131072,2304]{1,0} fusion(...)",
     "jit(_step)/jvp(KimiLinearModel)/layer_2/ffn/moe/bps.moe.route/gather:",
     "str", [4 * MS] * 2),
    ("%ragged-dot-metadata = (s32[9]{0}) custom-call(...)",
     "ragged-dot-metadata:", "str", [MS // 4] * 2),
    ("%ragged-dot-none.7 = bf16[131072,1024]{1,0} custom-call(...)",
     "jit(_step)/jvp(KimiLinearModel)/layer_2/ffn/moe/bps.moe.experts/"
     "ragged_dot:", "str", [MS] * 2),
    ("%fusion.8 = bf16[16384,1024]{1,0} fusion(...)",
     "jit(_step)/jvp(KimiLinearModel)/layer_2/ffn/moe/bps.moe.shared/shared/"
     "up/dot_general:", "str", [2 * MS] * 2),
    # the loops around the scan: one with no scope, as the chip writes it,
    # and one that names the scope it was opened under all the same
    ("%while.9 = (s32[], f32[1,32,128,128]) while(...)", "", None,
     [8 * MS] * 2),
    ("%while.10 = (s32[], f32[1,32,128,128]) while(...)",
     KDA + "bps.kda.scan/while:", "str", [8 * MS] * 2),
    ("%fusion.54 = f32[20480,2304]{1,0} fusion(...)",
     "jit(_step)/adamw/mul:", "str", [4 * MS] * 2),
]


def _run(tmp_path, trace, **more):
    return types.SimpleNamespace(
        trace={"steps": 2}, out_dir=str(tmp_path), layout=tr.TPU,
        probes={}, config=types.SimpleNamespace(), cfg=dict(LONG),
        rows=1, chips=1, **more)


def test_scopes_are_read_once_and_a_loop_is_not_counted_twice(
        tmp_path, monkeypatch):
    """Two steps; the programs' line reads 0.999 ms over them. KDA scan 5 + 3
    ms a step, prep 2, out 0.5; the attention 7; route 4 + 0.25 (the
    metadata helper), the kernel 1, shared 2; the two loops (16 ms a step)
    count nowhere. The capture is parsed twice (ops, programs) whichever
    readers ask, and however often."""
    trace = _capture(tmp_path, [_plane("/device:TPU:0", OPS)])
    ops = moe.scoped_ops(tr.find_xplane(trace), tr.TPU)
    assert kda.scoped_ms(ops, kda.SCOPES, 2) == {
        "scan": 8.0, "prep": 2.0, "out": 0.5}
    assert kda.scoped_ms(ops, kda.SCOPES, 0) == {}
    assert kda.scoped_ms([o for o in ops if o[0].startswith("%while")],
                         kda.SCOPES, 2) == {}
    reads = []
    monkeypatch.setattr(moe, "scoped_ops", lambda *a, real=moe.scoped_ops:
                        reads.append(a) or real(*a))
    monkeypatch.setattr("jax.devices", lambda: [types.SimpleNamespace(
        device_kind="TPU v5 lite")])
    run = _run(tmp_path, trace)
    run.probes["bps_moe_held_load"] = 0.9
    got = {**kda.read(run), **mla.read(run), **eshare.read(run)}
    assert len(reads) == 2
    programs_ms = 999_000_000 * 1e-9 / 2               # 0.4995 ms a step
    assert got == {
        "kda.scan_ms": 8.0, "kda.prep_ms": 2.0,
        "kda.layer_share_pct": pytest.approx(100 * 10.5 / programs_ms),
        "kda.scan_roofline_pct": pytest.approx(100 * 18.375 / 8.0,
                                               abs=1e-2),
        "mla.attend_ms": 7.0,
        "mla.attend_roofline_pct": pytest.approx(100 * 41.862 / 7.0,
                                                 abs=1e-2),
        "eshare.route_ms": 4.25, "eshare.gmm_ms": 1.0,
        "eshare.layer_share_pct": pytest.approx(100 * 7.25 / programs_ms),
        "eshare.held_load": 0.9}
    assert run.probes["kda_out_ms"] == 0.5
    assert run.probes["eshare_shared_ms"] == 2.0
    assert run.probes["mla_attend_share_pct"] == pytest.approx(
        100 * 7.0 / programs_ms)


def test_a_capture_without_the_scopes_reports_nothing(tmp_path):
    """As the parent's program is: the readers return nothing and do not
    raise, traced or not, probe or not."""
    trace = _capture(tmp_path, [_plane("/device:TPU:0", OPS[-1:])])
    run = _run(tmp_path, trace)
    assert kda.read(run) == {} and mla.read(run) == {}
    assert eshare.read(run) == {"eshare.held_load": None}
    run.trace = None
    assert kda.read(run) == {} and mla.read(run) == {}
    assert eshare.read(run) == {"eshare.held_load": None}
    for reader in (kda, eshare):
        reader.setup(run)               # no probe to run: nothing, no raise
        run.config = types.SimpleNamespace(layer_stats=None, FIRST={})
        reader.setup(run)
    assert run.probes == {}


def test_the_probes_publish_what_the_model_sowed(tmp_path):
    import numpy as np

    calls = []

    def layer_stats(cfg, rows):
        calls.append(rows)
        counts = np.full(256, 512, np.int32)
        counts[:8] = 256                     # the held experts at half load
        return {"moe_stats": {"layer_1": (counts,)},
                "kda_stats": {"layer_0": (np.float32(-91.5),),
                              "layer_1": (np.float32(-12.0),)}}

    run = _run(tmp_path, None)
    run.rows, run.chips, run.trace = 4, 4, None
    run.config = types.SimpleNamespace(layer_stats=layer_stats,
                                       FIRST={"seed": 1}, FIRST_EXPERT=0)
    kda.setup(run)
    eshare.setup(run)
    assert calls == [1, 1]                             # one chip's batch
    assert run.probes["bps_kda_min_chunk_log_decay"] == -91.5
    assert run.probes["bps_moe_held_load"] == pytest.approx(
        8 * 256 / ((8 * 256 + 248 * 512) * 8 / 256))
    assert eshare.read(run)["eshare.held_load"] == \
        run.probes["bps_moe_held_load"]


@pytest.mark.parametrize("reader,prefix,layer", [
    (kda, "kda.", "linear attention"), (mla, "mla.", "latent attention")])
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

    op, model, moe_py = (source("parallel", "linear_attention.py"),
                         source("models", "kimi_linear.py"),
                         source("parallel", "moe.py"))
    assert 'PREP_SCOPE, SCAN_SCOPE = "%s", "%s"' % (
        kda.SCOPES["prep"], kda.SCOPES["scan"]) in op
    assert 'KDA_OUT_SCOPE = "%s"' % kda.SCOPES["out"] in model
    assert 'MLA_ATTEND_SCOPE = "%s"' % mla.SCOPE in model
    assert 'SHARED_SCOPE = "%s"' % eshare.SCOPES["shared"] in model
    assert 'ROUTE_SCOPE = "%s"' % eshare.SCOPES["route"] in moe_py
    assert 'EXPERTS_SCOPE = "%s"' % eshare.SCOPES["experts_other"] in moe_py


# --------------------------------------------------------------------------
# What the builder's traced run recorded.

RECORDED = os.path.join(DATA, "collective-kda-1chip.scoped-ops.json.gz")


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as f:
        return json.load(f)


def test_the_readers_over_the_recorded_scoped_ops(recorded):
    """The capture's ops under their scopes, as the chip wrote them: the
    readers' sums are the traced line's, the scoped time is inside the
    programs' time, and the loops of the scan count nowhere."""
    steps = recorded["steps"]
    ops = [(name, tf_op, ps) for name, tf_op, ps, _ in recorded["ops"]]
    programs_ms = recorded["programs_ps"] * 1e-9 / steps
    want = recorded["traced_line"]
    got = kda.scoped_ms(ops, kda.SCOPES, steps)
    assert got["scan"] == pytest.approx(want["kda.scan_ms"], rel=1e-9)
    assert got["prep"] == pytest.approx(want["kda.prep_ms"], rel=1e-9)
    assert 100 * sum(got.values()) / programs_ms == pytest.approx(
        want["kda.layer_share_pct"], rel=1e-9)
    attend = kda.scoped_ms(ops, {"attend": mla.SCOPE}, steps)["attend"]
    assert attend == pytest.approx(want["mla.attend_ms"], rel=1e-9)
    assert mla.attend_roofline_pct(attend, CFG, 1, V5E) == pytest.approx(
        want["mla.attend_roofline_pct"], rel=1e-9)
    assert kda.scan_roofline_pct(got["scan"], CFG, 8_192, V5E) == \
        pytest.approx(want["kda.scan_roofline_pct"], rel=1e-9)
    assert 0 < want["kda.scan_roofline_pct"] < 100
    assert 0 < want["mla.attend_roofline_pct"] < 100
    loops = [o for o in ops if o[0].startswith(kda.CONTAINERS)]
    assert loops and kda.scoped_ms(loops, kda.SCOPES, steps) == {}
    # on the chip a loop carries no scope of its own
    assert not any(kda.SCOPES["scan"] in tf_op for _, tf_op, _ in loops)
    scoped = sum(got.values()) + attend
    assert scoped < programs_ms

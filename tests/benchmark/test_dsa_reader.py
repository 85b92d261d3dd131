"""The sparse-attention and expert-share readers (``benchmark/layers/dsa.py``,
``eshare.py``): their operations and bytes by hand at the cell's size, their
reading of a made-up ``.xplane.pb`` (encoded by ``test_moe_reader.py``'s
helpers, with hand-worked sums), and their reading of what the builder's own
traced run of ``keye-vl-2.0-30b-a3b.collective-dsa.1chip`` recorded (my chip
run, PR 33, seed 2147500077): the capture's scoped ops, equal ones summed,
cut by ``benchmark/layers/dsa.py``'s command, with that run's result line
beside them (``traced_line``), and the first step's event list, cut by
``benchmark/dump_events.py``. No JAX."""

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

from benchmark.layers import dsa, eshare, moe  # noqa: E402
from benchmark.lib import loop  # noqa: E402
from benchmark.lib import trace_reduce as tr  # noqa: E402

KEYE = {"hidden_size": 2048, "moe_intermediate_size": 768,
        "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128,
        "num_local_experts": 16, "num_hidden_layers": 4, "seq_len": 8192,
        "sa_config": {"topk": 2048}}
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
DATA = os.path.join(HERE, "data")


def test_attention_operations_and_bytes_by_hand():
    # query t attends min(t + 1, 2048) keys: a triangle, then 6144 full rows
    assert dsa.selected_pairs(8192, 2048) == 2048 * 2049 // 2 + 6144 * 2048 \
        == 14_681_088
    assert dsa.selected_pairs(2048, 2048) == 2048 * 2049 // 2   # all causal
    pair = 12 * 32 * 128        # QK and PV, forward + two gradients, a head
    assert dsa.attend_flops(1, 8192, 2048, 32, 128, 4) \
        == 4 * 14_681_088 * pair == 2_886_419_349_504
    # q and out 4096 wide, k and v 512: forward 4 tensors, backward 4 read
    # and 3 written, bf16
    per_token = (4096 + 512 + 512 + 4096) * 2 + (4096 + 512 + 512)
    assert dsa.attend_bytes(1, 8192, 32, 4, 128, 4) \
        == 4 * 8192 * per_token * 2 == 1_543_503_872
    least_ms = max(1e3 * 2_886_419_349_504 / 197e12,
                   1e3 * 1_543_503_872 / 819e9)
    assert least_ms == pytest.approx(14.6519, abs=1e-3)   # operations-bound
    assert dsa.attend_roofline_pct(317.0, KEYE, 1, V5E) == pytest.approx(
        100 * least_ms / 317.0)
    assert dsa.attend_roofline_pct(least_ms, KEYE, 1, V5E) == \
        pytest.approx(100.0)


def test_share_operations_and_bytes_by_hand():
    rows = 4 * 8192             # the even share: T k 16 / 128 a layer, 4 layers
    one = 2 * rows * 2048 * 768
    assert eshare.gmm_flops(rows, 2048, 768) == 9 * one == 927_712_935_936
    # per call: the rows at 2048 and at 768 and 16 experts' weights a layer
    per_call = 2 * (rows * (2048 + 768) + 4 * 16 * 2048 * 768)
    assert per_call == 385_875_968
    assert eshare.gmm_bytes(rows, 16, 2048, 768, 4) == 9 * per_call \
        == 3_472_883_712
    # 4.709 ms of operations against 4.240 ms of bytes: operations-bound,
    # by a tenth (512 rows an expert against weights of 2048 x 768)
    least_ms = max(1e3 * 9 * one / 197e12, 1e3 * 9 * per_call / 819e9)
    assert least_ms == pytest.approx(4.7092, abs=1e-3)
    assert eshare.gmm_roofline_pct(20.0, KEYE, rows, V5E, 4) == pytest.approx(
        100 * least_ms / 20.0)
    # no rows, no operations: the weights' bytes remain
    assert eshare.gmm_flops(0, 2048, 768) == 0


OPS = [
    ("%fusion.1 = f32[512,8192]{1,0} fusion(...)",
     "jit(_step)/jvp(KeyeModel)/layer_0/attn/while/body/checkpoint/"
     "bps.dsa.indexer/qjd,sd->qjs/dot_general:", "str", [3 * MS] * 2),
    ("%fusion.2 = f32[2048,1024]{1,0} fusion(...)",
     "jit(_step)/transpose(jvp(KeyeModel))/layer_0/attn/indexer/"
     "bps.dsa.indexer/q/dot_general:", "ref", [MS] * 2),
    ("%custom-call.3 = (f32[1,512,2048]{2,1,0}) custom-call(...)",
     "jit(_step)/jvp(KeyeModel)/layer_0/attn/while/body/checkpoint/"
     "bps.dsa.select/top_k:", "str", [2 * MS] * 2),
    ("%fusion.4 = f32[4,8,512]{2,1,0} fusion(...)",
     "jit(_step)/transpose(jvp(KeyeModel))/layer_0/attn/while/body/"
     "checkpoint/bps.dsa.attend/qcgd,scd->cgqs/dot_general:", "str",
     [8 * MS] * 2),
    ("%while.129 = (s32[], bf16[1,8192,4,128]) while(...)", "", None,
     [14 * MS] * 2),                       # the loop around them: no scope
    ("%ragged-dot-none.7 = bf16[65536,768]{1,0} custom-call(...)",
     "ragged-dot-none:", "str", [MS // 2] * 2),
    ("%gather.5 = bf16[65536,2048]{1,0} gather(...)",
     "jit(_step)/jvp(KeyeModel)/layer_0/moe/checkpoint/bps.moe.route/"
     "gather:", "str", [2 * MS] * 2),
    ("%fusion.54 = f32[2048,18992]{1,0} fusion(...)",
     "jit(_step)/transpose(jvp(KeyeModel))/lm_head/dot_general:", "str",
     [4 * MS] * 2),
]


def _run(tmp_path, trace, **more):
    return types.SimpleNamespace(
        trace={"steps": 2}, out_dir=str(tmp_path), layout=tr.TPU,
        probes={}, config=types.SimpleNamespace(), cfg=dict(KEYE),
        rows=1, chips=1, **more)


def test_scopes_are_read_from_the_capture(tmp_path):
    """Two steps. Indexer 3 + 1 ms a step, the selection 2, attention 8;
    the loop around them carries no scope and counts nowhere (it would
    count them twice); the expert layer's kernel and gather go to the other
    reader, the head to neither."""
    trace = _capture(tmp_path, [_plane("/device:TPU:0", OPS)])
    ops = moe.scoped_ops(tr.find_xplane(trace), tr.TPU)
    assert dsa.split_ms(ops, 2) == {"indexer": 4.0, "select": 2.0,
                                    "attend": 8.0}
    assert dsa.split_ms(ops, 0) == {}
    assert moe.split_ms(ops, 2) == {"gmm": 0.5, "route": 2.0,
                                    "experts_other": 0.0}


def test_a_capture_without_the_scopes_reports_nothing(tmp_path):
    trace = _capture(tmp_path, [_plane("/device:TPU:0", OPS[7:])])
    ops = moe.scoped_ops(tr.find_xplane(trace), tr.TPU)
    assert len(ops) == 2 and dsa.split_ms(ops, 2) == {}
    run = _run(tmp_path, trace)
    assert dsa.read(run) == {"dsa.kept_keys_pct": None}
    assert eshare.read(run) == {"eshare.held_load": None}
    run.trace = None
    assert dsa.read(run) == {"dsa.kept_keys_pct": None}
    assert eshare.read(run) == {"eshare.held_load": None}
    dsa.setup(run)                      # no probe to run: nothing, no raise
    eshare.setup(run)
    assert run.probes == {}
    # the probe's counter alone, from an untraced run
    run.probes["bps_dsa_kept_keys_ratio"] = 0.4375
    run.probes["bps_moe_held_load"] = 0.9
    assert dsa.read(run) == {"dsa.kept_keys_pct": 43.75}
    assert eshare.read(run) == {"eshare.held_load": 0.9}


def test_the_readers_declare_what_the_manifest_lists():
    assert dsa.LAYER == "sparse attention" and eshare.LAYER == "expert share"
    assert set(dsa.METRICS) == {
        "dsa.indexer_ms", "dsa.select_ms", "dsa.attend_ms",
        "dsa.layer_share_pct", "dsa.attend_roofline_pct",
        "dsa.kept_keys_pct"}
    assert set(eshare.METRICS) == {
        "eshare.gmm_ms", "eshare.router_ms", "eshare.route_ms",
        "eshare.layer_share_pct", "eshare.gmm_roofline_pct",
        "eshare.held_load"}
    for name in ("dsa.attend_roofline_pct", "eshare.gmm_roofline_pct"):
        metric = {**dsa.METRICS, **eshare.METRICS}[name]
        assert metric["better"] == "higher" and metric["moves"] == "mfu_pct"


# --------------------------------------------------------------------------
# What the builder's traced run recorded.

@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(
            DATA, "collective-dsa-1chip.scoped-ops.json.gz"), "rt") as f:
        return json.load(f)


def test_both_readers_over_the_recorded_scoped_ops(recorded):
    """The capture's ops under their scopes, as the chip wrote them: both
    readers' sums are the traced line's, the scoped time is inside the
    programs' time, and the loops that hold the attention's blocks carry no
    scope of their own."""
    steps = recorded["steps"]
    ops = [(name, tf_op, ps) for name, tf_op, ps, _ in recorded["ops"]]
    programs_ms = recorded["programs_ps"] * 1e-9 / steps
    got = dsa.split_ms(ops, steps)
    want = recorded["traced_line"]
    for key in ("indexer", "select", "attend"):
        assert got[key] == pytest.approx(want[f"dsa.{key}_ms"], rel=1e-9)
    assert 100 * sum(got.values()) / programs_ms == pytest.approx(
        want["dsa.layer_share_pct"], rel=1e-9)
    assert got["attend"] > got["indexer"] > 0 and got["select"] > 0
    share = eshare.split_ms(ops, steps)
    assert share["gmm"] == pytest.approx(want["eshare.gmm_ms"], rel=1e-9)
    assert share["route"] == pytest.approx(want["eshare.route_ms"], rel=1e-9)
    assert 100 * sum(share.values()) / programs_ms == pytest.approx(
        want["eshare.layer_share_pct"], rel=1e-9)
    assert sum(got.values()) + sum(share.values()) < programs_ms
    assert dsa.attend_roofline_pct(got["attend"], KEYE, 1, V5E) == \
        pytest.approx(want["dsa.attend_roofline_pct"], rel=1e-9)
    assert want["dsa.attend_roofline_pct"] < 100
    assert want["eshare.gmm_roofline_pct"] < 100
    loops = [ps for name, tf_op, ps in ops if name.startswith("%while")]
    assert loops and not any("bps.dsa" in tf_op for name, tf_op, _ in ops
                             if name.startswith("%while"))
    # four layers' grouped matmuls: 3 forward, 3 recomputed, 6 backward
    kernels = sum(n for name, _, _, n in recorded["ops"]
                  if moe.GMM_KERNEL.match(name))
    assert kernels == steps * 4 * 12


def test_the_recorded_step_reduces_to_one_program_a_step():
    with gzip.open(os.path.join(
            DATA, "collective-dsa-1chip-1step.events.json.gz"), "rt") as f:
        recorded = json.load(f)
    events = [tuple(e) for e in recorded["events"]]
    out = tr.reduce_events(events, steps=recorded["steps"], spans=loop.SPANS,
                           step_span=loop.STEP_SPAN, layout=tr.TPU)
    assert recorded["steps"] == 1 and out["steps"] == 1
    assert out["devices"] == 1 and out["programs_per_step"] == 1.0
    assert 0.7 < out["program_s_per_step"] < 1.0      # one 0.86 s program
    assert out["idle_share"] < 0.01
    assert out["collective_s_per_step"] == 0.0        # nothing leaves the chip

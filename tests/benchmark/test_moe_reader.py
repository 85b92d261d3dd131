"""The expert layer's reader (``benchmark/layers/moe.py``): its operations
and bytes by hand at OLMoE's size, and its reading of a capture against a
made-up ``.xplane.pb`` written here byte by byte (the fields of
``xplane.proto`` the reader walks), with hand-worked sums. No JAX."""

import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
from bench_tiny import REPO  # noqa: E402,F401

from benchmark.layers import moe  # noqa: E402
from benchmark.lib import trace_reduce as tr  # noqa: E402

OLMOE = {"hidden_size": 2048, "intermediate_size": 1024, "num_experts": 64,
         "num_experts_per_tok": 8, "num_hidden_layers": 1}
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_operations_and_bytes_by_hand():
    rows = 4096 * 8                     # assignments: exactly, no padding
    one = 2 * rows * 2048 * 1024        # one grouped matmul
    assert one == 137_438_953_472       # what the kernel's own stat says
    assert moe.gmm_calls(1) == 9        # gate, up, down x fwd, dgrad, wgrad
    assert moe.gmm_flops(4096, 8, 2048, 1024, 1) == 9 * one \
        == 1_236_950_581_248
    # per call: rows x 2048, rows x 1024 and 64 x 2048 x 1024, bf16, once
    per_call = 2 * (rows * 2048 + rows * 1024 + 64 * 2048 * 1024)
    assert per_call == 469_762_048
    assert moe.gmm_bytes(4096, 8, 64, 2048, 1024, 1) == 9 * per_call \
        == 4_227_858_432
    # 6.279 ms of operations against 5.162 ms of bytes: operations bound
    least_ms = max(1e3 * 9 * one / 197e12, 1e3 * 9 * per_call / 819e9)
    assert least_ms == pytest.approx(6.27893, abs=1e-4)
    assert moe.gmm_roofline_pct(17.883, OLMOE, 4096, V5E) == pytest.approx(
        100 * least_ms / 17.883)
    assert moe.gmm_roofline_pct(17.883, OLMOE, 4096, V5E) < 100
    # two layers: twice the work
    assert moe.gmm_flops(4096, 8, 2048, 1024, 2) == 18 * one


# --------------------------------------------------------------------------
# A made-up capture, encoded here.

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _int(field, value):
    return _varint(field << 3) + _varint(value)


def _bytes(field, payload):
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _plane(name, ops, line="XLA Ops"):
    """``ops``: (hlo text, tf_op, how the tf_op is stored, durations in ps).
    Stat metadata 1 is ``tf_op``; 2.. hold referenced strings."""
    stat_meta = {1: "tf_op"}
    event_meta, events = [], []
    for i, (hlo, tf_op, stored, durations) in enumerate(ops, start=1):
        if stored == "str":
            stat = _int(1, 1) + _bytes(5, tf_op)
        elif stored == "ref":
            ref = len(stat_meta) + 1
            stat_meta[ref] = tf_op
            stat = _int(1, 1) + _int(7, ref)
        else:
            stat = None
        meta = _int(1, i) + _bytes(2, hlo) + (
            _bytes(5, stat) if stat else b"")
        event_meta.append(_bytes(4, _int(1, i) + _bytes(2, meta)))
        events += [_bytes(4, _int(1, i) + _int(2, 1000) + _int(3, d))
                   for d in durations]
    body = _int(1, 7) + _bytes(2, name)
    body += _bytes(3, _int(1, 1) + _bytes(2, "XLA Modules")
                   + _bytes(4, _int(1, 1) + _int(3, 999_000_000)))
    body += _bytes(3, _int(1, 2) + _bytes(2, line) + b"".join(events))
    body += b"".join(event_meta)
    body += b"".join(_bytes(5, _int(1, k) + _bytes(2, _int(1, k) + _bytes(
        2, v))) for k, v in stat_meta.items())
    return _bytes(1, body)


MS = 1_000_000_000                      # picoseconds
OPS = [
    ("%ragged-dot-none.7 = bf16[32768,1024]{1,0} custom-call(...)",
     "ragged-dot-none:", "str", [2 * MS, 2 * MS]),
    ("%ragged-dot-metadata = (s32[65]{0}) custom-call(...)",
     "ragged-dot-metadata:", "str", [MS // 100] * 2),
    ("%fusion.1 = bf16[32768,2048]{1,0} fusion(...)",
     "jit(_step)/jvp(OlmoeModel)/layer_0/moe/bps.moe.route/gather:", "ref",
     [MS, MS]),
    ("%sort.3 = (s32[32768]{0}) sort(...)",
     "jit(_step)/transpose(jvp(OlmoeModel))/layer_0/moe/bps.moe.route/sort:",
     "str", [MS // 2] * 2),
    ("%copy.126 = bf16[64,2048,1024]{2,1,0} copy(...)",
     "jit(_step)/jvp(OlmoeModel)/layer_0/moe/bps.moe.experts/"
     "convert_element_type:", "str", [MS // 4] * 2),
    ("%fusion.54 = f32[2048,50304]{1,0} fusion(...)",
     "jit(_step)/transpose(jvp(OlmoeModel))/lm_head/dot_general:", "str",
     [6 * MS] * 2),
    ("%broadcast.498 = f32[4096]{0} broadcast(...)", "", None, [MS] * 2),
]


def _capture(tmp_path, planes):
    path = tmp_path / "trace" / "plugins" / "profile" / "now"
    path.mkdir(parents=True)
    (path / "host.xplane.pb").write_bytes(b"".join(planes))
    return str(tmp_path / "trace")


def test_scopes_and_kernels_are_read_from_the_capture(tmp_path):
    """Two steps. Kernels 2 ms a step; the route scope 1 + 0.5 ms and the
    kernels' metadata helper 0.01; the experts scope's cast 0.25; the head
    and an unscoped broadcast count nowhere. The second device's plane and
    the host's are not read."""
    trace = _capture(tmp_path, [
        _plane("/host:CPU", OPS, line="python3"),
        _plane("/device:TPU:1", [OPS[0]]),
        _plane("/device:TPU:0", OPS)])
    ops = moe.scoped_ops(tr.find_xplane(trace), tr.TPU)
    assert len(ops) == 14
    assert ops[0] == (OPS[0][0], "ragged-dot-none:", 2 * MS)
    assert ops[4][1].endswith("bps.moe.route/gather:")          # by reference
    assert moe.split_ms(ops, 2) == {"gmm": 2.0, "route": 1.51,
                                    "experts_other": 0.25}
    assert moe.split_ms(ops, 0) == {}

    # the capture's one program, 999 us, on the line of programs
    programs = moe.scoped_ops(tr.find_xplane(trace), tr.TPU, "XLA Modules")
    assert [d for _, _, d in programs] == [999_000_000]


def test_a_program_without_the_layer_reports_nothing(tmp_path):
    trace = _capture(tmp_path, [_plane("/device:TPU:0", OPS[5:])])
    ops = moe.scoped_ops(tr.find_xplane(trace), tr.TPU)
    assert len(ops) == 4 and moe.split_ms(ops, 2) == {}
    run = types.SimpleNamespace(trace={"steps": 2}, out_dir=str(tmp_path),
                                layout=tr.TPU,
                                probes={}, config=types.SimpleNamespace())
    assert moe.read(run) == {"moe.max_expert_load": None}
    run.trace = None
    assert moe.read(run) == {"moe.max_expert_load": None}
    moe.setup(run)                      # no probe to run: nothing, no raise
    assert run.probes == {}
    # a capture with no device plane at all (a CPU rehearsal)
    assert moe.scoped_ops(tr.find_xplane(_capture(
        tmp_path / "cpu", [_plane("/host:CPU", OPS)])), tr.TPU) == []


def test_the_reader_declares_what_the_manifest_lists():
    assert moe.LAYER == "expert layer"
    assert set(moe.METRICS) == {
        "moe.gmm_ms", "moe.route_ms", "moe.layer_share_pct",
        "moe.gmm_roofline_pct", "moe.max_expert_load"}
    assert moe.METRICS["moe.gmm_roofline_pct"]["better"] == "higher"
    assert moe.GMM_KERNEL.match("%ragged-dot-none.7 = bf16[8,8] custom-call")
    assert not moe.GMM_KERNEL.match("%ragged-dot-metadata.1 = (s32[65])")
    assert not moe.GMM_KERNEL.match("%fusion.1 = fusion(%ragged-dot-none.6)")

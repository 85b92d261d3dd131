"""The looped stack's reader (``benchmark/layers/loop.py``): its operations
and bytes by hand at the cell's size, its reading of a made-up
``.xplane.pb`` (encoded by ``test_moe_reader.py``'s helpers, with
hand-worked sums), and its reading of what the builder's own traced run of
``ouro-2.6b.collective-loop.1chip`` recorded (my chip run, PR 35): the
capture's scoped ops, equal ones summed, cut by ``benchmark/layers/
loop.py``'s command, with that run's result line beside them
(``traced_line``), and the first step's event list, cut by
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

from benchmark.layers import loop as reader  # noqa: E402
from benchmark.layers import moe  # noqa: E402
from benchmark.lib import cell as cell_lib  # noqa: E402
from benchmark.lib import loop as bench_loop  # noqa: E402
from benchmark.lib import trace_reduce as tr  # noqa: E402

OURO = {"hidden_size": 2048, "intermediate_size": 5632,
        "num_hidden_layers": 5, "total_ut_steps": 4, "seq_len": 4096}
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
DATA = os.path.join(HERE, "data")
CELL = "ouro-2.6b.collective-loop.1chip"


def test_stack_operations_and_bytes_by_hand():
    """The same count as ``configs/ouro-2.6b.py::flops_per_token``'s
    docstring: 358,612,992 operations a token a block application."""
    assert reader.block_matmul_params(2048, 5632) == 51_380_224
    per_token = 6 * 51_380_224 + 6 * 4096 * 2048
    assert per_token == 358_612_992
    assert reader.stack_flops(4096, 4096, 4, 5, 2048, 5632) \
        == 20 * per_token * 4096 == 29_377_576_304_640
    # an application: its weights twice in bf16, and 11 tensors [4096, 2048]
    per_application = 2 * (2 * 51_380_224 + 4096 * 11 * 2048)
    assert per_application == 390_070_272
    assert reader.stack_bytes(4096, 4, 5, 2048, 5632) \
        == 20 * per_application == 7_801_405_440
    # 149.1 ms of operations against 9.5 ms of bytes: operations-bound
    least_ms = max(1e3 * 29_377_576_304_640 / 197e12,
                   1e3 * 7_801_405_440 / 819e9)
    assert least_ms == pytest.approx(149.1247, abs=1e-3)
    assert reader.stack_roofline_pct(720.0, OURO, 1, V5E) == pytest.approx(
        100 * least_ms / 720.0)
    assert reader.stack_roofline_pct(least_ms, OURO, 1, V5E) == \
        pytest.approx(100.0)
    # the configuration's own count is this one and the exits'
    module = cell_lib.load_module(os.path.join(
        REPO, "benchmark", "configs", "ouro-2.6b.py"), "cfg_ouro")
    cfg = {**OURO, "vocab_size": 49_152}
    assert module.flops_per_token(cfg) * 4096 == \
        reader.stack_flops(4096, 4096, 4, 5, 2048, 5632) \
        + 4 * 4096 * 603_992_064 == 4096 * 9_588_228_096


STACK = ("jit(_step)/jvp(OuroModel)/while/body/bps.loop.stack/layer_0/"
         "checkpoint/")
OPS = [
    ("%fusion.1 = f32[1,16,4096,4096]{3,2,1,0} fusion(...)",
     STACK + "bqhd,bkhd->bhqk/dot_general:", "str", [5 * MS] * 2),
    ("%fusion.2 = bf16[4096,5632]{1,0} fusion(...)",
     "jit(_step)/transpose(jvp(OuroModel))/while/body/bps.loop.stack/"
     "layer_3/rematted_computation/mlp/up/dot_general:", "ref", [3 * MS] * 2),
    ("%fusion.3 = f32[4095,49152]{1,0} fusion(...)",
     "jit(_step)/jvp(OuroModel)/while/body/checkpoint/bps.loop.exit/lm_head/"
     "dot_general:", "str", [2 * MS] * 2),
    ("%fusion.4 = f32[] fusion(...)",
     "jit(_step)/jvp(bps.loop.exit)/reduce_sum:", "str", [MS // 2] * 2),
    # the loops around them: one with no scope, as the chip writes it, and
    # one that names a scope all the same
    ("%while.9 = (s32[], f32[1,4096,2048]) while(...)", "", None,
     [11 * MS] * 2),
    ("%while.10 = (s32[], f32[1,4096,2048]) while(...)",
     "jit(_step)/transpose(jvp(OuroModel))/bps.loop.stack/while:", "str",
     [11 * MS] * 2),
    ("%fusion.54 = f32[49152,2048]{1,0} fusion(...)",
     "jit(_step)/adamw/mul:", "str", [4 * MS] * 2),
]


def _run(tmp_path, trace, **more):
    return types.SimpleNamespace(
        trace={"steps": 2}, out_dir=str(tmp_path), layout=tr.TPU,
        probes={}, config=types.SimpleNamespace(), cfg=dict(OURO),
        rows=1, chips=1, **more)


def test_scopes_are_read_from_the_capture_and_a_loop_is_not_counted_twice(
        tmp_path):
    """Two steps. Stack 5 + 3 ms a step, exits 2 + 0.5; the two loops
    (22 ms a step between them) count nowhere, with a scope or without:
    their bodies' ops are events of their own."""
    trace = _capture(tmp_path, [_plane("/device:TPU:0", OPS)])
    ops = moe.scoped_ops(tr.find_xplane(trace), tr.TPU)
    assert len(ops) == 2 * len(OPS)
    assert reader.split_ms(ops, 2) == {"stack": 8.0, "exit": 2.5}
    assert reader.split_ms(ops, 0) == {}
    assert reader.split_ms([o for o in ops if o[0].startswith("%while")],
                           2) == {}


def test_a_capture_without_the_scopes_reports_nothing(tmp_path):
    trace = _capture(tmp_path, [_plane("/device:TPU:0", OPS[6:])])
    ops = moe.scoped_ops(tr.find_xplane(trace), tr.TPU)
    assert len(ops) == 2 and reader.split_ms(ops, 2) == {}
    run = _run(tmp_path, trace)
    assert reader.read(run) == {"loop.mean_exit_pass": None}
    run.trace = None
    assert reader.read(run) == {"loop.mean_exit_pass": None}
    reader.setup(run)                   # no probe to run: nothing, no raise
    assert run.probes == {}
    # a configuration with the probe but no first batch yet: nothing either
    run.config = types.SimpleNamespace(loop_stats=None, FIRST={})
    reader.setup(run)
    assert run.probes == {}
    # the probe's gauge alone, from an untraced run
    run.probes["bps_loop_mean_exit_pass"] = 1.875
    assert reader.read(run) == {"loop.mean_exit_pass": 1.875}


def test_the_probe_publishes_what_the_model_sowed(tmp_path):
    import numpy as np

    calls = []

    def loop_stats(cfg, rows):
        calls.append(rows)
        return {"exit_mean": (np.array([0.5, 0.25, 0.125, 0.125]),),
                "block_applications": (np.int32(16),)}

    run = _run(tmp_path, None)
    run.rows, run.chips = 4, 4
    run.config = types.SimpleNamespace(loop_stats=loop_stats,
                                       FIRST={"seed": 1})
    reader.setup(run)
    assert calls == [1]                                # one chip's batch
    assert run.probes == {"bps_loop_mean_exit_pass": 1.875,
                          "bps_loop_block_applications_total": 16.0,
                          "loop_block_applications": 16}


def test_the_reader_declares_what_the_manifest_lists():
    assert reader.LAYER == "looped stack"
    assert set(reader.METRICS) == {
        "loop.stack_ms", "loop.exit_ms", "loop.layer_share_pct",
        "loop.stack_roofline_pct", "loop.mean_exit_pass"}
    manifest = cell_lib.load_json(os.path.join(REPO, "BENCHMARK.json"))
    listed = {m["name"]: m for m in manifest["per_layer"]
              if m["name"].startswith("loop.")}
    assert set(listed) == set(reader.METRICS)
    for name, metric in listed.items():
        assert metric["layer"] == reader.LAYER
        assert metric["workloads"] == [CELL]
        assert {k: metric[k] for k in ("unit", "better", "source",
                                       "moves")} == reader.METRICS[name]
    roofline = reader.METRICS["loop.stack_roofline_pct"]
    assert roofline["better"] == "higher" and roofline["moves"] == "mfu_pct"
    # the scopes are the program's (read, not imported: no JAX here)
    with open(os.path.join(REPO, "byteps_tpu", "models", "ouro.py")) as f:
        program = f.read()
    assert 'STACK_SCOPE, EXIT_SCOPE = "%s", "%s"' % (
        reader.SCOPES["stack"], reader.SCOPES["exit"]) in program


# --------------------------------------------------------------------------
# What the builder's traced run recorded.

@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(
            DATA, "collective-loop-1chip.scoped-ops.json.gz"), "rt") as f:
        return json.load(f)


def test_the_reader_over_the_recorded_scoped_ops(recorded):
    """The capture's ops under their scopes, as the chip wrote them: the
    reader's sums are the traced line's, the scoped time is inside the
    programs' time and most of it, and the loops over the passes carry no
    scope of their own."""
    steps = recorded["steps"]
    ops = [(name, tf_op, ps) for name, tf_op, ps, _ in recorded["ops"]]
    programs_ms = recorded["programs_ps"] * 1e-9 / steps
    got = reader.split_ms(ops, steps)
    want = recorded["traced_line"]
    assert got["stack"] == pytest.approx(want["loop.stack_ms"], rel=1e-9)
    assert got["exit"] == pytest.approx(want["loop.exit_ms"], rel=1e-9)
    share = 100 * sum(got.values()) / programs_ms
    assert share == pytest.approx(want["loop.layer_share_pct"], rel=1e-9)
    assert 90 < share < 100 and got["stack"] > got["exit"] > 0
    assert reader.stack_roofline_pct(got["stack"], OURO, 1, V5E) == \
        pytest.approx(want["loop.stack_roofline_pct"], rel=1e-9)
    assert want["loop.stack_roofline_pct"] < 100
    assert want["loop.mean_exit_pass"] == pytest.approx(1.875, abs=0.01)
    loops = [(tf_op, ps) for name, tf_op, ps in ops
             if name.startswith("%while")]
    assert loops and not any("bps.loop" in tf_op for tf_op, _ in loops)
    # counted with the loops, the scoped time would pass the programs' time
    assert (sum(got.values()) + sum(ps for _, ps in loops) * 1e-9 / steps
            > programs_ms)


def test_the_recorded_step_reduces_to_one_program_a_step():
    with gzip.open(os.path.join(
            DATA, "collective-loop-1chip-1step.events.json.gz"), "rt") as f:
        recorded = json.load(f)
    events = [tuple(e) for e in recorded["events"]]
    out = tr.reduce_events(events, steps=recorded["steps"],
                           spans=bench_loop.SPANS,
                           step_span=bench_loop.STEP_SPAN, layout=tr.TPU)
    assert recorded["steps"] == 1 and out["steps"] == 1
    assert out["devices"] == 1 and out["programs_per_step"] == 1.0
    assert 0.5 < out["program_s_per_step"] < 1.0
    assert out["idle_share"] < 0.01
    assert out["collective_s_per_step"] == 0.0        # nothing leaves the chip

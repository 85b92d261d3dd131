"""The three readers of ``joyai-llm-flash.collective-mtp.1chip``
(``benchmark/layers/qmla.py``, ``mtp.py``, ``eshare.py``): the roofline's
operations and bytes by hand at the cell's size, their reading of a made-up
``.xplane.pb`` (encoded by ``test_moe_reader.py``'s helpers, with hand-worked
sums) through the one shared read of the capture — the module's block under
``bps.mtp`` counted by ``qmla`` / ``eshare`` and by ``mtp`` alike, the main
head by neither — and their reading of what the builder's own traced run of
the cell recorded (my chip run, PR 41, seed 2147483907): the capture's
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

from benchmark.layers import eshare, kda, mla, moe, mtp, qmla  # noqa: E402
from benchmark.lib import cell as cell_lib  # noqa: E402
from benchmark.lib import trace_reduce as tr  # noqa: E402

V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
DATA = os.path.join(HERE, "data")
CELL = "joyai-llm-flash.collective-mtp.1chip"
CFG = cell_lib.load_json(os.path.join(
    REPO, "benchmark", "configs", "joyai-llm-flash.json"))


def test_attend_roofline_by_hand():
    """``layers/qmla.py``'s docstring: five layers at s rows and the
    module's at the s - 2 with a target; the count is ``flops_per_token``'s
    attention term times the tokens."""
    s = CFG["seq_len"]
    assert (s, CFG["num_hidden_layers"], CFG["num_nextn_predict_layers"]) \
        == (8_192, 5, 1)
    main, module = s * (s + 1) // 2, (s - 2) * (s - 1) // 2
    assert (main, module) == (33_558_528, 33_542_145)
    pairs = 5 * main + module
    assert pairs == 201_334_785
    flops = pairs * 32 * 6 * (192 + 128)
    assert flops == 12_370_009_190_400
    assert flops == (mla.attend_flops(1, s, 32, 192, 128, 5)
                     + mla.attend_flops(1, s - 2, 32, 192, 128, 1))
    bytes_ = 2 * 32 * 2 * (192 + 128) * 2 * (5 * s + (s - 2))
    assert bytes_ == 4_026_368_000
    least_ms = max(1e3 * flops / 197e12, 1e3 * bytes_ / 819e9)
    assert least_ms == pytest.approx(62.792, abs=1e-3)     # arithmetic binds
    assert qmla.attend_roofline_pct(300.0, CFG, 1, V5E) == pytest.approx(
        100 * least_ms / 300.0)
    assert qmla.attend_roofline_pct(least_ms, CFG, 1, V5E) == \
        pytest.approx(100.0)
    # two rows a chip: twice the work
    assert qmla.attend_roofline_pct(300.0, CFG, 2, V5E) == pytest.approx(
        2 * 100 * least_ms / 300.0)


MAIN = "jit(_step)/jvp(JoyAIFlashModel)/layer_2/"
BACK = "jit(_step)/transpose(jvp(JoyAIFlashModel))/"
MODULE = BACK + "bps.mtp/mtp/block/jvp(JoyAIFlashModel)/bps.mtp/mtp/block/"
OPS = [
    ("%bps_flash_fwd.19 = (bf16[32,8192,128]) custom-call(...)",
     MAIN + "mixer/mla/bps.mla.attend/bps.attn.kernel/pallas_call:", "str",
     [10 * MS] * 2),
    ("%bps_flash_dkv.3 = (bf16[32,8192,192]) custom-call(...)",
     MODULE + "checkpoint/mixer/mla/bps.mla.attend/bps.attn.kernel/"
     "jit(_flash_bwd_impl)/pallas_call:", "ref", [6 * MS] * 2),
    ("%fusion.7 = bf16[8192,6144]{1,0} fusion(...)",
     MAIN + "mixer/mla/bps.mla.proj/q_b/dot_general:", "str", [3 * MS] * 2),
    ("%fusion.8 = f32[1,8192,32,64]{3,2,1,0} fusion(...)",
     MODULE + "checkpoint/mixer/mla/bps.mla.proj/mul:", "str", [MS] * 2),
    ("%fusion.9 = bf16[8192,2048]{1,0} fusion(...)",
     "jit(_step)/jvp(JoyAIFlashModel)/bps.mtp/mtp/bps.mtp.combine/eh_proj/"
     "dot_general:", "str", [MS // 2] * 2),
    # a head block: a call inside a scan, the module's under bps.mtp
    ("%fusion.5159 = (bf16[2048]{0}, f32[2048,16160]) fusion(...)",
     "jit(_step)/jvp(JoyAIFlashModel)/bps.mtp/while/body/closed_call/"
     "JoyAIFlashModel.<lambda>/JoyAIFlashModel._block_nll/bps.lm.head/"
     "lm_head/dot_general:", "str", [2 * MS] * 2),
    ("%fusion.5160 = (bf16[2048]{0}, f32[2048,16160]) fusion(...)",
     "jit(_step)/jvp(JoyAIFlashModel)/while/body/closed_call/"
     "JoyAIFlashModel.<lambda>/JoyAIFlashModel._block_nll/bps.lm.head/"
     "lm_head/dot_general:", "str", [2 * MS] * 2),
    ("%fusion.6 = bf16[65536,2048]{1,0} fusion(...)",
     MAIN + "ffn/moe/bps.moe.route/gather:", "str", [4 * MS] * 2),
    ("%ragged-dot-metadata = (s32[9]{0}) custom-call(...)",
     "ragged-dot-metadata:", "str", [MS // 4] * 2),
    ("%ragged-dot-none.7 = bf16[65536,768]{1,0} custom-call(...)",
     MODULE + "checkpoint/ffn/moe/bps.moe.experts/ragged_dot:", "str",
     [MS] * 2),
    ("%fusion.10 = bf16[8192,768]{1,0} fusion(...)",
     MAIN + "ffn/moe/bps.moe.shared/shared/up/dot_general:", "str",
     [2 * MS] * 2),
    # the head's scan, as the chip writes it: a container, counted nowhere
    ("%while.4 = (s32[], f32[4,2048]) while(...)",
     "jit(_step)/jvp(JoyAIFlashModel)/bps.mtp/while:", "str", [9 * MS] * 2),
    ("%fusion.54 = f32[16160,2048]{1,0} fusion(...)",
     "jit(_step)/adamw/mul:", "str", [4 * MS] * 2),
]


def _run(tmp_path, trace, **more):
    return types.SimpleNamespace(
        trace={"steps": 2}, out_dir=str(tmp_path), layout=tr.TPU,
        probes={}, config=types.SimpleNamespace(), cfg=dict(CFG),
        rows=1, chips=1, **more)


def test_the_module_s_block_counts_under_both_prefixes(tmp_path,
                                                       monkeypatch):
    """Two steps; the programs' line reads 0.999 ms over them. Attention 10
    (main) + 6 (the module's), projections 3 + 1; under ``bps.mtp``: 6 + 1 +
    0.5 (combine) + 2 (its head) + 1 (its experts) = 10.5, the scan around
    the head nowhere; route 4 + 0.25 (the metadata helper), the kernel 1,
    shared 2. The main head (2) is in no metric of these readers. The
    capture is parsed twice (ops, programs) whichever readers ask."""
    trace = _capture(tmp_path, [_plane("/device:TPU:0", OPS)])
    reads = []
    monkeypatch.setattr(moe, "scoped_ops", lambda *a, real=moe.scoped_ops:
                        reads.append(a) or real(*a))
    monkeypatch.setattr("jax.devices", lambda: [types.SimpleNamespace(
        device_kind="TPU v5 lite")])
    run = _run(tmp_path, trace)
    run.probes["bps_moe_held_load"] = 0.9
    got = {**qmla.read(run), **mtp.read(run), **eshare.read(run)}
    assert len(reads) == 2
    programs_ms = 999_000_000 * 1e-9 / 2               # 0.4995 ms a step
    assert got == {
        "qmla.attend_ms": 16.0, "qmla.proj_ms": 4.0,
        "qmla.layer_share_pct": pytest.approx(100 * 20.0 / programs_ms),
        "qmla.attend_roofline_pct": pytest.approx(100 * 62.792 / 16.0,
                                                  abs=1e-2),
        "mtp.module_ms": 10.5, "mtp.head_ms": 2.0,
        "mtp.share_pct": pytest.approx(100 * 10.5 / programs_ms),
        "eshare.route_ms": 4.25, "eshare.gmm_ms": 1.0,
        "eshare.layer_share_pct": pytest.approx(100 * 7.25 / programs_ms),
        "eshare.held_load": 0.9}
    assert run.probes["mtp_combine_ms"] == 0.5
    assert run.probes["eshare_shared_ms"] == 2.0


def test_a_capture_without_the_scopes_reports_nothing(tmp_path):
    """As the parent's program is: the readers return nothing and do not
    raise, traced or not, probe or not."""
    trace = _capture(tmp_path, [_plane("/device:TPU:0", OPS[-1:])])
    run = _run(tmp_path, trace)
    assert qmla.read(run) == {} and mtp.read(run) == {}
    assert eshare.read(run) == {"eshare.held_load": None}
    run.trace = None
    assert qmla.read(run) == {} and mtp.read(run) == {}
    assert eshare.read(run) == {"eshare.held_load": None}
    for reader in (mtp, eshare):
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
                "mtp_stats": {"main_loss": (np.float32(10.25),),
                              "next2_loss": (np.float32(10.5),)}}

    run = _run(tmp_path, None)
    run.rows, run.chips, run.trace = 4, 4, None
    run.config = types.SimpleNamespace(layer_stats=layer_stats,
                                       FIRST={"seed": 1}, FIRST_EXPERT=0)
    mtp.setup(run)
    eshare.setup(run)
    assert calls == [1, 1]                             # one chip's batch
    assert run.probes["bps_mtp_main_loss"] == 10.25
    assert run.probes["bps_mtp_next2_loss"] == 10.5
    assert run.probes["bps_moe_held_load"] == pytest.approx(
        8 * 256 / ((8 * 256 + 248 * 512) * 8 / 256))
    assert eshare.read(run)["eshare.held_load"] == \
        run.probes["bps_moe_held_load"]


@pytest.mark.parametrize("reader,prefix,layer", [
    (qmla, "qmla.", "rotary latent attention"),
    (mtp, "mtp.", "multi-token prediction")])
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

    shared, model = (source("models", "kimi_linear.py"),
                     source("models", "joyai.py"))
    assert 'MLA_ATTEND_SCOPE = "%s"' % qmla.SCOPES["attend"] in shared
    assert 'MLA_PROJ_SCOPE = "%s"' % qmla.SCOPES["proj"] in shared
    assert 'HEAD_SCOPE = "%s"' % mtp.SCOPES["head"] in shared
    assert 'MTP_SCOPE = "%s"' % mtp.SCOPE in model
    assert 'MTP_COMBINE_SCOPE = "%s"' % mtp.SCOPES["combine"] in model


# --------------------------------------------------------------------------
# What the builder's traced run recorded.

RECORDED = os.path.join(DATA, "collective-mtp-1chip.scoped-ops.json.gz")


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as f:
        return json.load(f)


def test_the_readers_over_the_recorded_scoped_ops(recorded):
    """The capture's ops under their scopes, as the chip wrote them: the
    readers' sums are the traced line's, every share is under 100%, and the
    module's attention is a sixth of all of it."""
    steps = recorded["steps"]
    ops = [(name, tf_op, ps) for name, tf_op, ps, _ in recorded["ops"]]
    programs_ms = recorded["programs_ps"] * 1e-9 / steps
    want = recorded["traced_line"]
    got = kda.scoped_ms(ops, qmla.SCOPES, steps)
    assert got["attend"] == pytest.approx(want["qmla.attend_ms"], rel=1e-9)
    assert got["proj"] == pytest.approx(want["qmla.proj_ms"], rel=1e-9)
    assert 100 * sum(got.values()) / programs_ms == pytest.approx(
        want["qmla.layer_share_pct"], rel=1e-9)
    assert qmla.attend_roofline_pct(got["attend"], CFG, 1, V5E) == \
        pytest.approx(want["qmla.attend_roofline_pct"], rel=1e-9)
    assert 0 < want["qmla.attend_roofline_pct"] < 100
    inside = [op for op in ops if mtp.SCOPE in op[1]]
    module = kda.scoped_ms(inside, {"module": mtp.SCOPE}, steps)["module"]
    assert module == pytest.approx(want["mtp.module_ms"], rel=1e-9)
    head = kda.scoped_ms(inside, mtp.SCOPES, steps)["head"]
    assert head == pytest.approx(want["mtp.head_ms"], rel=1e-9)
    assert 100 * module / programs_ms == pytest.approx(
        want["mtp.share_pct"], rel=1e-9)
    for share in ("qmla.layer_share_pct", "mtp.share_pct"):
        assert 0 < want[share] < 100


def test_the_nesting_in_the_recorded_capture(recorded):
    """``bps.mtp`` lies over the module's ``bps.mla.attend``, ``bps.mla.
    proj``, ``bps.moe.route`` and ``bps.lm.head``: one of six mixers, one
    of five expert layers, one of two passes through the head."""
    steps = recorded["steps"]
    ops = [(name, tf_op, ps) for name, tf_op, ps, _ in recorded["ops"]]
    inside = [op for op in ops if mtp.SCOPE in op[1]]
    scopes = {"attend": "bps.mla.attend", "route": "bps.moe.route",
              "head": "bps.lm.head"}
    whole, module = (kda.scoped_ms(some, scopes, steps)
                     for some in (ops, inside))
    assert module["attend"] / whole["attend"] == pytest.approx(1 / 6,
                                                               rel=0.02)
    assert module["route"] / whole["route"] == pytest.approx(1 / 5, rel=0.1)
    assert module["head"] / whole["head"] == pytest.approx(1 / 2, rel=0.02)
    # the scans around the head blocks are containers, and carry the scope
    # they were opened under or none: counted nowhere
    loops = [o for o in ops if o[0].startswith(kda.CONTAINERS)]
    assert loops and kda.scoped_ms(loops, {"module": mtp.SCOPE}, steps) == {}

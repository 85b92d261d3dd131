"""The two readers of ``zaya1-8b.collective-cca.1chip``
(``benchmark/layers/cca.py``, ``eshare.py``): the rooflines' operations and
bytes by hand at the cell's size, their reading of a made-up ``.xplane.pb``
(encoded by ``test_moe_reader.py``'s helpers, with hand-worked sums) through
the one shared read of the capture, and their reading of what the builder's
own traced run of the cell recorded (my chip run, PR 55): the capture's
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

from benchmark.layers import cca, eshare, kda, moe, swa  # noqa: E402
from benchmark.lib import cell as cell_lib  # noqa: E402
from benchmark.lib import trace_reduce as tr  # noqa: E402

V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
DATA = os.path.join(HERE, "data")
CELL = "zaya1-8b.collective-cca.1chip"
CFG = cell_lib.load_json(os.path.join(
    REPO, "benchmark", "configs", "zaya1-8b.json"))


def test_the_attention_s_roofline_by_hand():
    """``layers/cca.py``'s docstring: five layers of 8 heads 128 wide over
    the causal triangle, in the latent; bound by arithmetic."""
    s = CFG["seq_len"]
    assert (s, CFG["num_hidden_layers"]) == (16_384, 5)
    triangle = s * (s + 1) // 2
    flops = swa.attend_flops(1, s, 8, 128)
    assert flops == 8 * 1536 * triangle == 1_649_368_104_960
    nbytes = swa.attend_bytes(1, s, 8, 2, 128)
    assert nbytes == 2 * 2 * s * (2 * 8 + 2 * 2) * 128 == 167_772_160
    flops_ms = 5 * 1e3 * flops / 197e12
    assert flops_ms == pytest.approx(41.862, abs=1e-3)
    assert 5 * 1e3 * nbytes / 819e9 < flops_ms             # arithmetic binds
    assert cca.attend_roofline_pct(90.0, CFG, 1, V5E) == pytest.approx(
        100 * flops_ms / 90.0)
    # the whole depth: eight times the layers
    assert cca.attend_roofline_pct(
        90.0, {**CFG, "num_hidden_layers": 40}, 1, V5E) == pytest.approx(
        8 * 100 * flops_ms / 90.0)


def test_the_grouped_matmuls_roofline_by_hand():
    """``eshare.gmm_roofline_pct`` at this cell's shapes: the rows
    that reached the 8 held experts, nine calls, the held weights only. At
    even routing 5 x 8,192 rows through 2048 x 2048: bound by arithmetic,
    the one share cell that is."""
    rows = 5 * 8_192
    flops = eshare.gmm_flops(rows, 2048, 2048)
    assert flops == 9 * 2 * rows * 2048 * 2048 == 3_092_376_453_120
    nbytes = eshare.gmm_bytes(rows, 8, 2048, 2048, 5)
    assert nbytes == 9 * 2 * (rows * 4096 + 5 * 8 * 2048 * 2048) \
        == 6_039_797_760
    flops_ms = 1e3 * flops / 197e12
    assert flops_ms == pytest.approx(15.697, abs=1e-3)
    assert 1e3 * nbytes / 819e9 < flops_ms                 # arithmetic binds
    assert eshare.gmm_roofline_pct(40.0, CFG, rows, V5E, 5) == pytest.approx(
        100 * flops_ms / 40.0)


FWD = "jit(_step)/jvp(ZayaModel)/layer_%d/"
BACK = "jit(_step)/transpose(jvp(ZayaModel))/layer_%d/"
OPS = [
    ("%bps_flash_fwd.3 = (bf16[8,16384,128]) custom-call(...)",
     FWD % 0 + "mixer/cca/bps.cca.attend/bps.attn.kernel/pallas_call:",
     "str", [5 * MS] * 2),
    ("%bps_flash_dkv.1 = (bf16[2,16384,128]) custom-call(...)",
     BACK % 0 + "checkpoint/mixer/cca/bps.cca.attend/bps.attn.kernel/"
     "jit(_flash_bwd_impl)/pallas_call:", "ref", [15 * MS] * 2),
    ("%fusion.1 = f32[1,16384,10,128]{3,2,1,0} fusion(...)",
     FWD % 1 + "mixer/cca/bps.cca.mix/bshc,hcd->bshd/dot_general:", "str",
     [3 * MS] * 2),
    ("%fusion.2 = f32[1,16384,1280]{2,1,0} fusion(...)",
     BACK % 1 + "checkpoint/mixer/cca/bps.cca.mix/mul:", "ref",
     [4 * MS] * 2),
    ("%fusion.3 = bf16[16384,1024]{1,0} fusion(...)",
     FWD % 2 + "mixer/cca/bps.cca.proj/q/dot_general:", "str", [2 * MS] * 2),
    ("%fusion.4 = f32[16384,256]{1,0} fusion(...)",
     FWD % 2 + "ffn/moe/router/bps.moe.router/down/dot_general:", "str",
     [MS] * 2),
    ("%fusion.5 = f32[256,256]{1,0} fusion(...)",
     BACK % 2 + "checkpoint/ffn/moe/router/bps.moe.router/mlp_1/"
     "dot_general:", "ref", [MS // 2] * 2),
    ("%fusion.6 = bf16[16384,2048]{1,0} fusion(...)",
     FWD % 2 + "ffn/moe/bps.moe.route/gather:", "str", [4 * MS] * 2),
    ("%ragged-dot-metadata = (s32[9]{0}) custom-call(...)",
     "ragged-dot-metadata:", "str", [MS // 4] * 2),
    ("%ragged-dot-none.7 = bf16[16384,2048]{1,0} custom-call(...)",
     BACK % 3 + "checkpoint/ffn/moe/bps.moe.experts/ragged_dot:", "str",
     [8 * MS] * 2),
    ("%fusion.9 = bf16[8,2048,2048]{2,1,0} fusion(...)",
     FWD % 0 + "ffn/moe/bps.moe.experts/convert_element_type:", "str",
     [MS // 2] * 2),
    ("%fusion.54 = f32[32784,2048]{1,0} fusion(...)",
     "jit(_step)/adamw/mul:", "str", [4 * MS] * 2),
]


def _run(tmp_path, trace, **more):
    return types.SimpleNamespace(
        trace={"steps": 2}, out_dir=str(tmp_path), layout=tr.TPU,
        probes={}, config=types.SimpleNamespace(), cfg=dict(CFG),
        rows=1, chips=1, **more)


def test_each_layer_counts_under_its_own_scope(tmp_path, monkeypatch):
    """Two steps; the programs' line reads 0.999 ms over them. Attend 5 +
    15, mix 3 + 4, proj 2; the router 1 + 0.5, which the expert layer's own
    split (``layers/moe.py``: 4 + 0.25, the metadata helper, + 1.5) holds in
    the route and ``eshare.route_ms`` does not; grouped matmuls 8, the
    experts' casts 0.5; the layer's share is all of it, router included."""
    trace = _capture(tmp_path, [_plane("/device:TPU:0", OPS)])
    monkeypatch.setattr("jax.devices", lambda: [types.SimpleNamespace(
        device_kind="TPU v5 lite")])
    run = _run(tmp_path, trace)
    run.probes.update(bps_moe_held_load=0.97, eshare_held_rows=40_960,
                      eshare_expert_layers=5)
    got = {**cca.read(run), **eshare.read(run)}
    programs_ms = 999_000_000 * 1e-9 / 2               # 0.4995 ms a step
    assert got == {
        "cca.attend_ms": 20.0, "cca.mix_ms": 7.0, "cca.proj_ms": 2.0,
        "cca.layer_share_pct": pytest.approx(100 * 29.0 / programs_ms),
        "cca.attend_roofline_pct": pytest.approx(100 * 41.862 / 20.0,
                                                 abs=1e-2),
        "eshare.router_ms": 1.5, "eshare.route_ms": 4.25,
        "eshare.gmm_ms": 8.0,
        "eshare.gmm_roofline_pct": pytest.approx(100 * 15.697 / 8.0,
                                                 abs=1e-2),
        "eshare.layer_share_pct": pytest.approx(100 * 14.25 / programs_ms),
        "eshare.held_load": 0.97}
    assert moe.split_ms(kda.capture_ms(run)[0], 2)["route"] == 5.75


def test_a_capture_without_the_scopes_reports_nothing(tmp_path):
    """As the parent's program is: no scope, no kernel, no collection; the
    readers return nothing that has a value and do not raise, traced or
    not."""
    trace = _capture(tmp_path, [_plane("/device:TPU:0", OPS[-1:])])
    run = _run(tmp_path, trace)
    assert cca.read(run) == {}
    assert eshare.read(run) == {"eshare.held_load": None}
    run.trace = None
    assert cca.read(run) == {}
    assert eshare.read(run) == {"eshare.held_load": None}
    eshare.setup(run)                    # no probe to run: nothing, no raise
    run.config = types.SimpleNamespace(layer_stats=lambda cfg, rows: {},
                                       FIRST={})
    eshare.setup(run)
    assert run.probes == {}


@pytest.mark.parametrize("reader,prefix", [(cca, "cca.")])
def test_the_readers_declare_what_the_manifest_lists(reader, prefix):
    manifest = cell_lib.load_json(os.path.join(REPO, "BENCHMARK.json"))
    listed = {m["name"]: m for m in manifest["per_layer"]
              if m["name"].startswith(prefix)}
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
    with open(os.path.join(REPO, "byteps_tpu", "models", "zaya.py")) as f:
        model = f.read()
    for key, name in (("proj", "CCA_PROJ_SCOPE"), ("mix", "CCA_MIX_SCOPE"),
                      ("attend", "CCA_ATTEND_SCOPE")):
        assert '%s = "%s"' % (name, cca.SCOPES[key]) in model
    assert 'ROUTER_SCOPE = "%s"' % eshare.SCOPES["router"] in model


# --------------------------------------------------------------------------
# What the builder's traced run recorded.

RECORDED = os.path.join(DATA, "collective-cca-1chip.scoped-ops.json.gz")


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as f:
        return json.load(f)


def test_the_readers_over_the_recorded_scoped_ops(recorded):
    """The capture's ops under their scopes, as the chip wrote them: the
    readers' sums are the traced line's, and every share and both rooflines
    are between 0 and 100%."""
    steps = recorded["steps"]
    ops = [(name, tf_op, ps) for name, tf_op, ps, _ in recorded["ops"]]
    programs_ms = recorded["programs_ps"] * 1e-9 / steps
    want = recorded["traced_line"]
    got = kda.scoped_ms(ops, cca.SCOPES, steps)
    for key in ("attend", "mix", "proj"):
        assert got[key] == pytest.approx(want[f"cca.{key}_ms"], rel=1e-9)
    assert 100 * sum(got.values()) / programs_ms == pytest.approx(
        want["cca.layer_share_pct"], rel=1e-9)
    assert cca.attend_roofline_pct(got["attend"], CFG, 1, V5E) \
        == pytest.approx(want["cca.attend_roofline_pct"], rel=1e-9)
    for name in ("cca.attend_roofline_pct", "cca.layer_share_pct"):
        assert 0 < want[name] < 100, name
    # the expert layers beside them (``test_eshare_reader.py`` holds their
    # figures over this list): the two layers together inside the step
    share = eshare.split_ms(ops, steps)
    assert sum(got.values()) + sum(share.values()) < programs_ms


def test_the_kernels_in_the_recorded_capture(recorded):
    """Every layer's flash kernels lie under the mixer's scope and inside
    ``bps.attn.kernel``: the forward twice (the mixer half is recomputed)
    at the 8 query heads, dQ at the query heads and dK/dV at the 2 key
    heads (the group summed inside the kernel), all 128 wide, five layers;
    nothing under ``bps.cca.mix`` is a kernel of this repo."""
    calls = {}
    for name, tf_op, _, count in recorded["ops"]:
        if cca.SCOPES["mix"] in tf_op:
            assert "bps_" not in name and "custom-call" not in name
        if "bps_flash" not in name:
            continue
        assert "bps.attn.kernel" in tf_op and cca.SCOPES["attend"] in tf_op
        kernel = name.split("=")[0].strip("% ").split(".")[0]
        shape = name.split("bf16[")[1].split("]")[0].split(",")
        assert shape[-1] == "128"
        calls[kernel, int(shape[0])] = calls.get(
            (kernel, int(shape[0])), 0) + count // recorded["steps"]
    assert calls == {("bps_flash_fwd", 8): 10, ("bps_flash_dq", 8): 5,
                     ("bps_flash_dkv", 2): 5}

"""The one reader of every gated expert share (``benchmark/layers/
eshare.py``) in the seven cells that report it: its split of a made-up
``.xplane.pb`` (encoded by ``test_moe_reader.py``'s helpers, with hand-worked
sums), its counts by hand where a stack's depth is not its count of expert
layers, and its reading of what the builders' own traced runs of the seven
cells recorded under ``data/`` (the captures' scoped ops, equal ones summed,
with each run's result line beside them under the prefix the cell's reader
then had: the first field of ``CELLS``). Until PR 70 the cells had a copy of the reader each; every case
their tests held is held here of the one. No JAX."""

import ast
import gzip
import json
import os
import re
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
from bench_tiny import REPO  # noqa: E402,F401
from test_moe_reader import MS, _capture, _plane  # noqa: E402

from benchmark.layers import eshare, kda, moe  # noqa: E402
from benchmark.lib import cell as cell_lib  # noqa: E402
from benchmark.lib import trace_reduce as tr  # noqa: E402

V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
MANIFEST = cell_lib.load_json(os.path.join(REPO, "BENCHMARK.json"))
# the prefixes the copies had
GONE = ("smoe", "lmoe", "wmoe", "nmoe", "zmoe", "mmoe")
# traffic -> (the prefix on the recorded line, expert layers, the keys that
# hold top-k and the number of experts in the configuration's file)
CELLS = {
    "collective-dsa.1chip": ("eshare", 4, "num_experts_per_tok",
                             "num_experts"),
    "collective-kda.1chip": ("smoe", 4, "num_experts_per_token",
                             "num_experts"),
    "collective-mtp.1chip": ("lmoe", 5, "num_experts_per_tok",
                             "n_routed_experts"),
    "collective-swa.1chip": ("wmoe", 4, "num_experts_per_tok",
                             "num_experts"),
    "collective-gdn.1chip": ("nmoe", 4, "num_experts_per_tok",
                             "num_experts"),
    "collective-cca.1chip": ("zmoe", 5, "num_experts_per_tok",
                             "num_experts"),
    "collective-swa-moe.1chip": ("mmoe", 4, "num_experts_per_tok",
                                 "num_experts"),
}
# the copies that summed the share by scope alone, the grouped matmuls left
# out, and those that declared no figure of the kernels at all
SHARE_BY_SCOPE_ALONE = ("smoe", "lmoe", "wmoe", "nmoe")
NO_KERNELS_ON_THE_LINE = ("smoe", "lmoe", "wmoe")


def _cell(traffic):
    return next(c for c in MANIFEST["workloads"] if c["traffic"] == traffic)


def _cfg(traffic):
    return cell_lib.load_json(os.path.join(
        REPO, cell_lib.find_config(MANIFEST, _cell(traffic))["file"]))


def _listed(cell_name):
    return sorted(m["name"] for m in MANIFEST["per_layer"]
                  if m["name"].startswith("eshare.")
                  and cell_name in m["workloads"])


def _run(tmp_path, cfg, **probes):
    return types.SimpleNamespace(
        trace={"steps": 2}, out_dir=str(tmp_path), layout=tr.TPU,
        probes=dict(probes), config=types.SimpleNamespace(), cfg=dict(cfg),
        rows=1, chips=1)


@pytest.fixture
def v5e(monkeypatch):
    monkeypatch.setattr("jax.devices", lambda: [types.SimpleNamespace(
        device_kind="TPU v5 lite")])


# --------------------------------------------------------------------------
# The counts, by hand.

@pytest.mark.parametrize("traffic,rows,flops,nbytes,least_ms,by_depth_ms", [
    ("collective-kda.1chip", 8_192, 347_892_350_976, 1_849_688_064, 2.2585,
     2.8231),
    ("collective-mtp.1chip", 10_240, 289_910_292_480, 1_651_507_200, 2.0165,
     2.0165),
    ("collective-swa.1chip", 16_384, 309_237_645_312, 1_962_934_272, 2.3967,
     2.9959)])
def test_expert_layers_are_the_probe_s_count_not_the_depth(
        traffic, rows, flops, nbytes, least_ms, by_depth_ms):
    """``layers/eshare.py``'s docstring: the three cells that report the
    grouped matmuls' roofline first in PR 70 have a leading dense layer, 4
    expert layers in a stack of 5 (one of them a fifth in its multi-token
    module), and the held weights of a layer no kernel reads are not in the
    bytes: at even routing all three are bound by bandwidth, where the
    layers count."""
    cfg = _cfg(traffic)
    _, layers, top_k, experts = CELLS[traffic]
    d, m, held = (cfg["hidden_size"], cfg["moe_intermediate_size"],
                  cfg["num_local_experts"])
    assert cfg["num_hidden_layers"] == 5
    tokens = cfg["batch_per_chip"] * cfg["seq_len"]
    assert rows == layers * tokens * cfg[top_k] * held // cfg[experts]
    assert eshare.gmm_flops(rows, d, m) == 9 * 2 * rows * d * m == flops
    assert eshare.gmm_bytes(rows, held, d, m, layers) == 9 * 2 * (
        rows * (d + m) + layers * held * d * m) == nbytes
    assert 1e3 * flops / 197e12 < 1e3 * nbytes / 819e9       # bandwidth
    assert 1e3 * nbytes / 819e9 == pytest.approx(least_ms, abs=1e-4)
    assert eshare.gmm_roofline_pct(10.0, cfg, rows, V5E, layers) == \
        pytest.approx(100 * least_ms / 10.0, abs=1e-3)
    # what counting the stack's depth would have read
    deep = cfg["num_hidden_layers"]
    assert eshare.gmm_roofline_pct(
        10.0, cfg, deep * rows // layers, V5E, deep) == pytest.approx(
        100 * by_depth_ms / 10.0, abs=1e-3)


def test_setup_counts_the_layers_and_the_held_rows_of_the_probe(tmp_path):
    """Three expert layers sowed counts in a stack of five: the probe's
    leaves are the layers, their held slices the rows, whatever the
    configuration's depth says."""
    import numpy as np

    calls = []

    def layer_stats(cfg, rows):
        calls.append(rows)
        counts = np.full(256, 512, np.int32)
        counts[:8] = 256                     # the held experts at half load
        return {"moe_stats": {f"layer_{i}": {"moe": {"counts": (counts,)}}
                              for i in (1, 2, 4)},
                "kda_stats": {"layer_0": (np.float32(-91.5),)}}

    run = _run(tmp_path, {"num_hidden_layers": 5, "num_local_experts": 8})
    run.rows, run.chips, run.trace = 4, 4, None
    run.config = types.SimpleNamespace(layer_stats=layer_stats,
                                       FIRST={"seed": 1}, FIRST_EXPERT=0)
    eshare.setup(run)
    assert calls == [1]                                # one chip's batch
    assert run.probes["eshare_expert_layers"] == 3
    assert run.probes["eshare_held_rows"] == 3 * 8 * 256
    assert run.probes["bps_moe_held_load"] == pytest.approx(
        8 * 256 / ((8 * 256 + 248 * 512) * 8 / 256))
    assert eshare.read(run) == {
        "eshare.held_load": run.probes["bps_moe_held_load"]}
    # another slice of the experts is held: its rows, the same layers
    run.config.FIRST_EXPERT, run.probes = 8, {}
    eshare.setup(run)
    assert run.probes["eshare_held_rows"] == 3 * 8 * 512


# --------------------------------------------------------------------------
# A made-up capture.

FWD = "jit(_step)/jvp(Model)/layer_%d/"
BACK = "jit(_step)/transpose(jvp(Model))/layer_%d/"
ROUTER = [
    ("%fusion.4 = f32[16384,256]{1,0} fusion(...)",
     FWD % 2 + "ffn/moe/router/bps.moe.router/down/dot_general:", "str",
     [MS] * 2),
    ("%fusion.5 = f32[256,256]{1,0} fusion(...)",
     BACK % 2 + "checkpoint/ffn/moe/router/bps.moe.router/mlp_1/"
     "dot_general:", "ref", [MS // 2] * 2),
]
SHARED = [
    ("%fusion.8 = bf16[16384,1024]{1,0} fusion(...)",
     FWD % 2 + "ffn/moe/bps.moe.shared/shared/up/dot_general:", "str",
     [2 * MS] * 2),
]
OPS = [
    ("%fusion.6 = bf16[131072,2048]{1,0} fusion(...)",
     FWD % 2 + "ffn/moe/bps.moe.route/gather:", "str", [4 * MS] * 2),
    ("%ragged-dot-metadata = (s32[9]{0}) custom-call(...)",
     "ragged-dot-metadata:", "str", [MS // 4] * 2),
    # as the chip writes a share's pass: the kernel under no scope at all
    ("%ragged-dot-none.7 = bf16[16384,2048]{1,0} custom-call(...)",
     BACK % 3 + "checkpoint/ffn/moe/cond/branch_1_fun/jit(_held_pass)/"
     "ragged-dot:", "str", [8 * MS] * 2),
    ("%fusion.9 = bf16[8,2048,2048]{2,1,0} fusion(...)",
     FWD % 0 + "ffn/moe/bps.moe.experts/convert_element_type:", "str",
     [MS // 2] * 2),
    # containers: one with no scope, as the chip writes them, and one that
    # names the scope it was opened under all the same
    ("%conditional.3 = (bf16[16384,2048]) conditional(...)", "", None,
     [9 * MS] * 2),
    ("%while.10 = (s32[], bf16[16384,2048]) while(...)",
     FWD % 2 + "ffn/moe/bps.moe.route/while:", "str", [3 * MS] * 2),
    ("%fusion.54 = f32[32784,2048]{1,0} fusion(...)",
     "jit(_step)/adamw/mul:", "str", [4 * MS] * 2),
]
PROGRAMS_MS = 999_000_000 * 1e-9 / 2                   # 0.4995 ms a step
CFG = {"hidden_size": 2048, "moe_intermediate_size": 2048,
       "num_local_experts": 8, "num_hidden_layers": 5}
PROBED = dict(bps_moe_held_load=0.97, eshare_held_rows=40_960,
              eshare_expert_layers=5)


def test_every_op_goes_to_one_part_and_a_container_to_none(tmp_path):
    """Two steps. The router 1 + 0.5 ms a step under the longer scope, the
    route 4 + 0.25 (the metadata helper) under the shorter, the kernels 8
    by name, the experts' casts 0.5, the shared expert 2; the loop and the
    conditional count nowhere, the optimizer nowhere."""
    trace = _capture(tmp_path, [_plane("/device:TPU:0",
                                       ROUTER + SHARED + OPS)])
    ops = moe.scoped_ops(tr.find_xplane(trace), tr.TPU)
    assert eshare.split_ms(ops, 2) == {
        "gmm": 8.0, "router": 1.5, "route": 4.25, "experts_other": 0.5,
        "shared": 2.0}
    assert eshare.split_ms(ops, 0) == {}
    assert eshare.split_ms(
        [o for o in ops if o[0].startswith(kda.CONTAINERS)], 2) == {}
    # the expert layer's own split holds the router in the route: the one
    # scope's name begins with the other's
    assert moe.split_ms(ops, 2)["route"] == 4.25 + 1.5 + 3.0
    assert list(eshare.SCOPES)[0] == "router"
    assert eshare.SCOPES["router"].startswith(eshare.SCOPES["route"])


def test_a_router_s_scope_is_taken_off_the_route_and_reported(tmp_path, v5e):
    _capture(tmp_path, [_plane("/device:TPU:0", ROUTER + OPS)])
    run = _run(tmp_path, CFG, **PROBED)
    assert eshare.read(run) == {
        "eshare.router_ms": 1.5, "eshare.route_ms": 4.25,
        "eshare.gmm_ms": 8.0,
        "eshare.gmm_roofline_pct": pytest.approx(100 * 15.697 / 8.0,
                                                 abs=1e-2),
        "eshare.layer_share_pct": pytest.approx(100 * 14.25 / PROGRAMS_MS),
        "eshare.held_load": 0.97}
    assert run.probes["eshare_experts_other_ms"] == 0.5
    assert run.probes["eshare_shared_ms"] == 0.0


def test_a_program_without_a_router_s_scope_reports_no_router_ms(
        tmp_path, v5e):
    _capture(tmp_path, [_plane("/device:TPU:0", OPS)])
    got = eshare.read(_run(tmp_path, CFG, **PROBED))
    assert "eshare.router_ms" not in got
    assert got["eshare.route_ms"] == 4.25
    assert got["eshare.layer_share_pct"] == pytest.approx(
        100 * 12.75 / PROGRAMS_MS)


def test_a_shared_expert_counts_in_the_share(tmp_path, v5e):
    _capture(tmp_path, [_plane("/device:TPU:0", SHARED + OPS)])
    run = _run(tmp_path, CFG, **PROBED)
    got = eshare.read(run)
    assert run.probes["eshare_shared_ms"] == 2.0
    assert got["eshare.layer_share_pct"] == pytest.approx(
        100 * 14.75 / PROGRAMS_MS)
    assert got["eshare.route_ms"] == 4.25 and got["eshare.gmm_ms"] == 8.0


def test_the_share_holds_the_kernels_that_carry_no_scope(tmp_path, v5e):
    """The case the sums by scope failed: the kernel's ``tf_op`` holds no
    ``bps.moe.experts``, and its 8 ms are in the share all the same."""
    _capture(tmp_path, [_plane("/device:TPU:0", SHARED + OPS)])
    ops, programs_ms, steps = kda.capture_ms(_run(tmp_path, CFG))
    kernels = [o for o in ops if moe.GMM_KERNEL.match(o[0])]
    assert kernels and not any("bps.moe" in tf_op for _, tf_op, _ in kernels)
    by_scope = kda.scoped_ms(ops, {k: eshare.SCOPES[k] for k in (
        "route", "experts_other", "shared")}, steps)
    assert sum(by_scope.values()) == 4.0 + 0.5 + 2.0   # nor the helper
    ms = eshare.split_ms(ops, steps)
    assert sum(ms.values()) - sum(by_scope.values()) == 8.0 + 0.25


def test_the_roofline_waits_for_the_probe_and_the_kernels(tmp_path, v5e):
    """The kernels' time is reported wherever they run; their roofline needs
    the probe's rows and layers, and nothing is reported of kernels that are
    not there."""
    _capture(tmp_path, [_plane("/device:TPU:0", OPS)])
    got = eshare.read(_run(tmp_path, CFG, bps_moe_held_load=0.97))
    assert got["eshare.gmm_ms"] == 8.0
    assert "eshare.gmm_roofline_pct" not in got
    five = eshare.read(_run(tmp_path, CFG, **PROBED))
    four = eshare.read(_run(tmp_path, CFG, **{**PROBED,
                                              "eshare_expert_layers": 4}))
    # 40,960 rows through 2048 x 2048 are bound by arithmetic: the layers'
    # weights are in the bytes alone
    assert five["eshare.gmm_roofline_pct"] == four["eshare.gmm_roofline_pct"]
    # a tenth of the rows through 2048 x 512 are bound by bandwidth
    small, few = ({**CFG, "moe_intermediate_size": 512},
                  {**PROBED, "eshare_held_rows": 4_096})
    five = eshare.read(_run(tmp_path, small, **few))
    four = eshare.read(_run(tmp_path, small, **{**few,
                                                "eshare_expert_layers": 4}))
    assert five["eshare.gmm_roofline_pct"] > four["eshare.gmm_roofline_pct"]
    no_kernels = tmp_path / "without"
    _capture(no_kernels, [_plane("/device:TPU:0", OPS[:2] + OPS[3:])])
    got = eshare.read(_run(no_kernels, CFG, **PROBED))
    assert "eshare.gmm_ms" not in got and "eshare.gmm_roofline_pct" not in got
    assert got["eshare.layer_share_pct"] == pytest.approx(
        100 * 4.75 / PROGRAMS_MS)


def test_a_capture_without_the_layer_reports_nothing(tmp_path):
    """No scope, no kernel, no collection: nothing that has a value and no
    raise, traced or not, probe or not."""
    _capture(tmp_path, [_plane("/device:TPU:0", OPS[-1:])])
    run = _run(tmp_path, CFG)
    assert eshare.read(run) == {"eshare.held_load": None}
    run.trace = None
    assert eshare.read(run) == {"eshare.held_load": None}
    eshare.setup(run)                   # no probe to run: nothing, no raise
    run.config = types.SimpleNamespace(layer_stats=lambda cfg, rows: {},
                                       FIRST={})
    eshare.setup(run)
    assert run.probes == {}
    run.probes["bps_moe_held_load"] = 0.9   # the counter alone, untraced
    assert eshare.read(run) == {"eshare.held_load": 0.9}


def test_the_scopes_are_the_program_s():
    """Read, not imported: no JAX here."""
    def source(*path):
        with open(os.path.join(REPO, "byteps_tpu", *path)) as f:
            return f.read()

    moe_py = source("parallel", "moe.py")
    assert 'ROUTE_SCOPE = "%s"' % eshare.SCOPES["route"] in moe_py
    assert 'EXPERTS_SCOPE = "%s"' % eshare.SCOPES["experts_other"] in moe_py
    assert 'SHARED_SCOPE = "%s"' % eshare.SCOPES["shared"] in source(
        "models", "kimi_linear.py")
    assert 'ROUTER_SCOPE = "%s"' % eshare.SCOPES["router"] in source(
        "models", "zaya.py")
    assert (moe.ROUTE_SCOPE, moe.EXPERTS_SCOPE) == (
        eshare.SCOPES["route"], eshare.SCOPES["experts_other"])


# --------------------------------------------------------------------------
# The manifest and the files.

def test_the_reader_declares_what_the_manifest_lists():
    listed = {m["name"]: m for m in MANIFEST["per_layer"]
              if m["name"].startswith("eshare.")}
    assert eshare.LAYER == "expert share"
    assert set(listed) == set(eshare.METRICS) == {
        "eshare.gmm_ms", "eshare.router_ms", "eshare.route_ms",
        "eshare.layer_share_pct", "eshare.gmm_roofline_pct",
        "eshare.held_load"}
    seven = [_cell(traffic)["name"] for traffic in CELLS]
    for name, metric in listed.items():
        assert metric["layer"] == eshare.LAYER
        assert {k: metric[k] for k in ("unit", "better", "source",
                                       "moves")} == eshare.METRICS[name]
        assert metric["workloads"] == (
            [_cell("collective-cca.1chip")["name"]]
            if name == "eshare.router_ms" else seven)
    roofline = listed["eshare.gmm_roofline_pct"]
    assert (roofline["unit"], roofline["better"], roofline["moves"]) == (
        "%", "higher", "mfu_pct")


@pytest.mark.parametrize("traffic", list(CELLS))
def test_a_cell_s_traffic_file_names_the_one_reader(traffic):
    readers = cell_lib.load_json(os.path.join(
        REPO, "benchmark", "traffic", traffic + ".json"))["readers"]
    assert readers.count("eshare") == 1 and not set(readers) & set(GONE)
    assert "share" in readers             # the same probe at the window's end
    want = 6 if traffic == "collective-cca.1chip" else 5
    assert len(_listed(_cell(traffic)["name"])) == want


# a document outside the benchmark names this one's path, and a test outside
# it holds the path to exist: the file stays as a docstring and nothing else
SIGNPOST = "zmoe"


@pytest.mark.parametrize("prefix", GONE)
def test_a_copy_of_the_reader_is_gone(prefix):
    """Its code, its entries, and its name in every file a run loads."""
    bench = os.path.join(REPO, "benchmark")
    own = os.path.join(bench, "layers", prefix + ".py")
    if prefix == SIGNPOST:
        with open(own) as f:
            (only,) = ast.parse(f.read()).body
        assert isinstance(only, ast.Expr) and isinstance(only.value.value, str)
    else:
        assert not os.path.exists(own)
    assert not any(m["name"].startswith(prefix + ".")
                   for m in MANIFEST["per_layer"])
    word = re.compile(r"\b%s\b" % prefix)              # not ``olmoe``
    for folder in ("layers", "traffic", "configs", "lib"):
        for name in sorted(os.listdir(os.path.join(bench, folder))):
            path = os.path.join(bench, folder, name)
            if os.path.isfile(path) and path != own:
                with open(path, errors="replace") as f:
                    assert not word.search(f.read()), path


def test_no_reader_of_a_share_holds_a_cell_s_or_a_configuration_s_name():
    names = {c["name"] for c in MANIFEST["workloads"]} | {
        c["name"] for c in MANIFEST["configs"]}
    for reader in ("eshare", "rmoe"):
        with open(os.path.join(REPO, "benchmark", "layers",
                               reader + ".py")) as f:
            text = f.read()
        assert not [n for n in names if n in text]
        assert not [p for p in GONE if re.search(r"\b%s\b" % p, text)]


# --------------------------------------------------------------------------
# What the builders' traced runs recorded.

def _recorded(traffic):
    name = traffic.replace(".", "-") + ".scoped-ops.json.gz"
    with gzip.open(os.path.join(HERE, "data", name), "rt") as f:
        return json.load(f)


def _held_rows(traffic, held_load):
    """The rows behind a load: its even part is T k H / E a layer, and the
    quotient of two whole numbers gives the dividend back exactly."""
    _, layers, top_k, experts = CELLS[traffic]
    cfg = _cfg(traffic)
    rows = held_load * (
        layers * cfg["batch_per_chip"] * cfg["seq_len"] * cfg[top_k]
        * cfg["num_local_experts"] // cfg[experts])
    assert rows == int(rows)
    return int(rows)


def _read_the_recorded(traffic, monkeypatch):
    """``eshare.read`` over the recorded list, the probe's figures from the
    recorded line: (what it returned, the line under its old names, the
    list's own sums)."""
    recorded = _recorded(traffic)
    old, layers = CELLS[traffic][:2]
    line = {name.partition(".")[2]: value
            for name, value in recorded["traced_line"].items()
            if name.startswith(old + ".")}
    cfg = _cfg(traffic)
    held_rows = _held_rows(traffic, line["held_load"])
    assert held_rows == recorded.get("held_rows", held_rows)
    steps = recorded["steps"]
    ops = [(name, tf_op, ps) for name, tf_op, ps, _ in recorded["ops"]]
    programs_ms = recorded["programs_ps"] * 1e-9 / steps
    monkeypatch.setattr(kda, "capture_ms",
                        lambda run: (ops, programs_ms, steps))
    monkeypatch.setattr("jax.devices", lambda: [types.SimpleNamespace(
        device_kind="TPU v5 lite")])
    run = types.SimpleNamespace(
        trace={"steps": steps}, cfg=cfg, probes=dict(
            bps_moe_held_load=line["held_load"],
            eshare_held_rows=held_rows, eshare_expert_layers=layers))
    return eshare.read(run), line, (ops, programs_ms, steps)


@pytest.mark.parametrize("traffic,metric", [
    (traffic, name) for traffic in CELLS
    for name in _listed(_cell(traffic)["name"])])
def test_a_metric_over_a_cell_s_recorded_scoped_ops(traffic, metric,
                                                    monkeypatch):
    """Every figure the manifest promises of the cell is on the line, and
    reads what the cell's own copy of the reader read in the run that was
    recorded, to the digit — but the two corrections of PR 70: the share of
    the four cells that summed by scope alone rises by its grouped matmuls
    over the programs' time and by nothing else, and the three that
    declared no kernels report them, under their roofline."""
    got, line, (ops, programs_ms, steps) = _read_the_recorded(
        traffic, monkeypatch)
    old, layers = CELLS[traffic][:2]
    rest = metric.partition(".")[2]
    assert metric in got, sorted(got)
    kernels_ms = moe.split_ms(ops, steps)["gmm"]
    assert kernels_ms > 0
    if rest == "layer_share_pct" and old in SHARE_BY_SCOPE_ALONE:
        assert got[metric] == pytest.approx(
            line[rest] + 100 * kernels_ms / programs_ms, rel=1e-9)
    elif rest in line:
        assert got[metric] == pytest.approx(line[rest], rel=1e-9)
    else:
        assert old in NO_KERNELS_ON_THE_LINE
        assert got["eshare.gmm_ms"] == pytest.approx(kernels_ms, rel=1e-9)
        assert 0 < got["eshare.gmm_roofline_pct"] < 100
        assert got["eshare.gmm_roofline_pct"] == eshare.gmm_roofline_pct(
            kernels_ms, _cfg(traffic),
            _held_rows(traffic, line["held_load"]), V5E, layers)


@pytest.mark.parametrize("traffic", list(CELLS))
def test_the_parts_over_a_cell_s_recorded_scoped_ops(traffic, monkeypatch):
    """The five parts against the two sums the copies were made of
    (``layers/moe.py::split_ms`` and ``layers/kda.py::scoped_ms``): the
    same figures wherever a definition did not change, no op in two parts,
    the kernels on the chip under no scope, a router's scope only where the
    program has one, a shared expert's only where it has one, and the whole
    inside the programs' time."""
    got, line, (ops, programs_ms, steps) = _read_the_recorded(
        traffic, monkeypatch)
    ms = eshare.split_ms(ops, steps)
    split = moe.split_ms(ops, steps)
    scoped = kda.scoped_ms(ops, {"router": eshare.SCOPES["router"],
                                 "shared": eshare.SCOPES["shared"]}, steps)
    scoped = scoped or {"router": 0.0, "shared": 0.0}
    assert ms["gmm"] == split["gmm"]
    assert ms["experts_other"] == split["experts_other"]
    assert ms["router"] == scoped["router"]
    assert ms["shared"] == scoped["shared"]
    assert ms["route"] == pytest.approx(split["route"] - scoped["router"],
                                        rel=1e-12)
    assert sum(ms.values()) == pytest.approx(
        sum(split.values()) + scoped["shared"], rel=1e-12)
    assert got["eshare.layer_share_pct"] == pytest.approx(
        100 * sum(ms.values()) / programs_ms)
    assert 0 < got["eshare.layer_share_pct"] < 100
    assert not any("bps.moe" in tf_op for name, tf_op, _ in ops
                   if moe.GMM_KERNEL.match(name))
    assert not any("bps.moe" in tf_op for name, tf_op, _ in ops
                   if name.startswith(kda.CONTAINERS))
    has_router = traffic == "collective-cca.1chip"
    assert (ms["router"] > 0) == has_router == ("eshare.router_ms" in got)
    shared_width = ("num_shared_experts", "n_shared_experts",
                    "shared_expert_intermediate_size")
    assert (ms["shared"] > 0) == any(_cfg(traffic).get(k)
                                     for k in shared_width)
    assert 0.5 < got["eshare.held_load"] < 2.0

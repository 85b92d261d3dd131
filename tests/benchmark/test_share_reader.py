"""The reader of the share cells' window (``benchmark/layers/share.py``):
the drift of a recorded ``Window``'s step series by hand — rising, falling,
flat, and too short to have two ends, which reads nothing and never 0 —,
the held load of made-up counts, and ``read`` / ``finish`` on a run that
has and has not what they look for. No JAX but for ``finish``'s one case,
which applies a made-up model on the CPU."""

import os
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
from bench_tiny import REPO  # noqa: E402,F401

from benchmark.layers import share  # noqa: E402
from benchmark.lib import cell as cell_lib  # noqa: E402
from benchmark.lib import loop  # noqa: E402

MANIFEST = cell_lib.load_json(os.path.join(REPO, "BENCHMARK.json"))
TRAFFIC = {"trace_steps": 8, "log_every": 4}
# seconds a step, one entry a log interval; the first two are the traced
# steps' (slower under the profiler), which no end of the window counts
SERIES = {
    # 0.60, 0.60 at the start and 0.84, 0.90 at the end: 1.74 / 1.20 - 1
    "rising": ([0.70, 0.71, 0.60, 0.60, 0.66, 0.72, 0.78, 0.84, 0.90], 45.0),
    # Nemotron's way: 0.80, 0.76 -> 0.74, 0.74: 1.48 / 1.56 - 1
    "falling": ([0.90, 0.90, 0.80, 0.76, 0.75, 0.74, 0.74], -5.128205128),
    "flat": ([0.62, 0.61, 0.5, 0.5, 0.5, 0.5, 0.5, 0.5], 0.0),
    # four after the traced two: the two ends touch and do not overlap
    "six_intervals": ([0.9, 0.9, 0.50, 0.50, 0.55, 0.55], 10.0),
}


def _run(step_s, probes=None, traffic=TRAFFIC):
    return types.SimpleNamespace(
        window=loop.Window(attempted=4 * len(step_s),
                           completed=4 * len(step_s), step_s=list(step_s)),
        traffic=traffic, probes=dict(probes or {}))


@pytest.mark.parametrize("name", list(SERIES))
def test_drift_of_a_recorded_window_by_hand(name):
    step_s, want = SERIES[name]
    assert share.step_drift_pct(step_s, 2) == pytest.approx(want, abs=1e-6)
    got = share.read(_run(step_s))
    assert got["share.step_drift_pct"] == pytest.approx(want, abs=1e-6)
    assert got["share.held_load_end"] is None    # no probe ran


@pytest.mark.parametrize("step_s,traced", [
    ([0.7, 0.7, 0.6, 0.6, 0.6], 2),        # five intervals in all
    ([0.7, 0.7, 0.6], 2), ([], 2),
    ([0.7] * 4 + [0.6] * 3, 4),            # seven, but three after the traced
])
def test_a_window_too_short_reads_nothing_and_not_zero(step_s, traced):
    assert share.step_drift_pct(step_s, traced) is None
    run = _run(step_s, traffic={"trace_steps": 4 * traced, "log_every": 4})
    assert share.read(run)["share.step_drift_pct"] is None


def test_the_traced_intervals_follow_the_traffic_file():
    """10 traced steps at a fetch every 4 are three intervals (4, 4, 2)."""
    step_s = [9.0, 9.0, 9.0, 1.0, 1.0, 1.0, 1.0, 2.0, 2.0]
    run = _run(step_s, traffic={"trace_steps": 10, "log_every": 4})
    assert share.read(run)["share.step_drift_pct"] == pytest.approx(100.0)
    assert share.read(types.SimpleNamespace(
        window=None, traffic=TRAFFIC, probes={"share_held_load_end": 1.5})) \
        == {"share.held_load_end": 1.5}


def test_held_load_by_hand():
    """Two layers over 8 experts, experts 2..3 held: 30 + 50 of the 100 +
    200 assignments reached them, their even part is 300 x 2 / 8 = 75."""
    a = np.array([10, 10, 10, 20, 10, 10, 10, 20])
    b = np.array([25, 25, 25, 25, 25, 25, 25, 25])
    assert share.held_load([a, b], 2, 2) == pytest.approx(80 / 75)
    assert share.held_load([a], 2, 2) == pytest.approx(30 / 25)
    assert share.held_load([b], 0, 8) == pytest.approx(1.0)
    assert share.held_load([np.array([0, 0, 9, 0])], 0, 2) == 0.0


def test_the_manifest_lists_the_eight_share_cells_for_both():
    entries = {m["name"]: m for m in MANIFEST["per_layer"]
               if m["name"].startswith("share.")}
    assert set(entries) == set(share.METRICS)
    for m in entries.values():
        assert m["layer"] == share.LAYER and m["moves"] == "step_ms_p50"
        assert len(m["workloads"]) == 8
        assert m["workloads"] == entries["share.step_drift_pct"]["workloads"]


def test_finish_probes_the_state_it_is_given():
    """A made-up model whose routing is its one parameter: ``finish`` reads
    the held load of the parameters handed to it — the window's last — and
    not of the initialisation; a configuration without a model, a first
    batch or the collection leaves the probes alone."""
    import flax.linen as nn
    import jax.numpy as jnp

    class Routed(nn.Module):
        @nn.compact
        def __call__(self, tokens):
            w = self.param("w", nn.initializers.zeros, (4,))
            counts = jnp.zeros(4).at[jnp.argmax(w)].add(tokens.size)
            self.sow("moe_stats", "counts", counts)
            return tokens

    class Plain(nn.Module):
        @nn.compact
        def __call__(self, tokens):
            return tokens * self.param("w", nn.initializers.ones, ())

    tokens = np.zeros((2, 6), np.int32)
    config = types.SimpleNamespace(
        _model=lambda cfg: Routed(), FIRST={"tokens": tokens},
        FIRST_EXPERT=2)
    run = types.SimpleNamespace(config=config, cfg={"num_local_experts": 2},
                                rows=2, chips=1, probes={})
    start = {"params": {"w": jnp.zeros(4)}}               # expert 0: not held
    end = {"params": {"w": jnp.array([0., 0., 0., 5.])}}  # expert 3: held
    share.finish(run, (start, None))
    assert run.probes["share_held_load_end"] == 0.0
    share.finish(run, (end, None))
    assert run.probes["share_held_load_end"] == pytest.approx(2.0)
    assert run.probes["share_held_load_end_by_layer"] == [pytest.approx(2.0)]
    for other in (types.SimpleNamespace(FIRST={"tokens": tokens}),
                  types.SimpleNamespace(_model=lambda cfg: Routed(), FIRST={}),
                  types.SimpleNamespace(_model=lambda cfg: Plain(),
                                        FIRST={"tokens": tokens},
                                        FIRST_EXPERT=0)):
        bare = types.SimpleNamespace(config=other, rows=2, chips=1,
                                     cfg={"num_local_experts": 2}, probes={})
        share.finish(bare, ({"params": {"w": jnp.ones(())}}, None))
        assert bare.probes == {}

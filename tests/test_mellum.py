"""MellumModel and what it brought (tier-1, CPU, float32, seeded): YaRN over
the whole head on the global layers of a 3 : 1 stack of windowed and global
grouped-query attention with one head count, a softmax top-k expert layer
with no shared expert in every block, a share of a quarter of the experts
at two held assignments a token.

The yardstick shares no code with the program: ``benchmark/lib/
plain_mellum.py`` (the rotation as a complex multiplication, YaRN's ramp in
numpy, attention in query blocks over all keys with the band as a mask and
the group as an axis, a literal ``argsort`` gate over dense experts, the
share as ``(first_expert, held)``). In float32 on the CPU both sides differ
by the order sums are taken in: 1e-5 of the loss and of a gradient leaf's
largest entry; nothing discrete can flip at these sizes and seeds. The
controls show what that tolerance fails.
"""

import hashlib
import importlib
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from byteps_tpu.models import (JoyAIFlashTiny, KimiLinearTiny, LagunaTiny,
                               Mellum2_12B, MellumTiny, Qwen3NextTiny,
                               mellum_loss)
from byteps_tpu.models.kimi_linear import KimiSparseMoe
from byteps_tpu.models.llama import yarn_inv_freq, yarn_ramp
from byteps_tpu.models.mellum import MELLUM_SITES
from byteps_tpu.monitor import metrics
from byteps_tpu.parallel import moe as moe_lib
from byteps_tpu.parallel.moe import (dropless_moe_ffn, held_row_bound,
                                     held_row_rungs, publish_moe_stats)
from byteps_tpu.parallel.ring_attention import (KERNEL_SITES, WINDOW_NEEDED,
                                                WINDOW_SITES, WINDOW_WALKED,
                                                XLA_SITES)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.lib import cell as cell_lib  # noqa: E402
from benchmark.lib import plain_mellum as plain  # noqa: E402

CONFIG = os.path.join(REPO, "benchmark", "configs", "mellum2-12b-a2.5b")
FACTOR = 1.2772588722239782
YARN = {"rope_type": "yarn", "rope_theta": 500000.0, "factor": 16.0,
        "original_max_position_embeddings": 65536, "beta_fast": 32.0,
        "beta_slow": 1.0, "attention_factor": FACTOR}
DEFAULT = {"rope_type": "default", "rope_theta": 500000.0}
ROPE = {"full_attention": YARN, "sliding_attention": DEFAULT}
PLAIN = dict(head_dim=16, window=8, rope_parameters=ROPE, top_k=2,
             share=(0, 2), eps=1e-6, dtype=jnp.float32, query_block=8,
             head_rows=32)


def _rel(got, want):
    return float(jnp.abs(got - want).max()) / max(
        float(jnp.abs(want).max()), 1e-30)


@pytest.fixture(autouse=True)
def _highest():
    """float32 matmuls at float32 on both sides of every comparison."""
    with jax.default_matmul_precision("highest"):
        yield


# --------------------------------------------------------------------------
# the rotation

def test_yarn_over_the_whole_head_by_hand():
    """The global layers' 128 rotated entries: r(beta) = 128 ln(8192 / (2 pi
    beta)) / (2 ln 500000); r(32) = 18.08 and r(1) = 34.98, so the ramp runs
    from pair 18 to pair 35: pairs 0..18 keep their own frequency, pairs
    35..63 are divided by 16, pair 26 is 8/17 of the way."""
    two_ln = 2 * math.log(500000.0)
    assert round(128 * math.log(8192 / (2 * math.pi * 32)) / two_ln, 2) \
        == 18.08
    assert round(128 * math.log(8192 / (2 * math.pi)) / two_ln, 2) == 34.98
    assert yarn_ramp(128, 500000.0, 8192, 32.0, 1.0) == (18, 35)
    w = np.asarray(yarn_inv_freq(128, 500000.0, 16.0, 8192, 32.0, 1.0))
    own = 500000.0 ** (-np.arange(64) / 64.0)
    assert w.shape == (64,) and w[0] == 1.0
    np.testing.assert_allclose(w[:19], own[:19], rtol=1e-6)
    np.testing.assert_allclose(w[35:], own[35:] / 16, rtol=1e-6)
    np.testing.assert_allclose(
        w[26], own[26] * (9 / 17 + 8 / 17 / 16), rtol=1e-6)
    # the attention factor is the source's own: 0.1 ln(16) + 1
    assert abs(0.1 * math.log(16.0) + 1 - FACTOR) < 1e-12
    # the plain reference's ramp, written apart, is the same
    rotary, freqs, factor = plain.rotary_of(
        {**YARN, "original_max_position_embeddings": 8192}, 128)
    assert (rotary, factor) == (128, FACTOR)
    np.testing.assert_allclose(freqs, w, rtol=1e-6)
    rotary, freqs, factor = plain.rotary_of(DEFAULT, 128)
    assert (rotary, factor) == (128, 1.0)
    np.testing.assert_allclose(freqs, own, rtol=1e-6)
    # the tiny model's ramp lies inside its 8 pairs
    assert yarn_ramp(16, 500000.0, 65536, 32.0, 1.0) == (3, 6)


def test_both_kinds_rotate_the_whole_head():
    """A windowed layer's rotary is ``theta``'s own over all of a head; a
    global layer's is YaRN's over all of it, times the factor: a rotated
    row's norm is the factor times the old one, and no entry passes."""
    model = MellumTiny()
    x = jnp.asarray(np.random.default_rng(0).standard_normal((1, 12, 3, 16)),
                    jnp.float32)
    window, full = model.window_rotary(x), model.full_rotary(x)
    np.testing.assert_allclose(jnp.linalg.norm(window, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    np.testing.assert_allclose(jnp.linalg.norm(full, axis=-1),
                               FACTOR * jnp.linalg.norm(x, axis=-1),
                               rtol=1e-5)
    assert not jnp.any(full[0, 1:] == x[0, 1:])
    for rotary, group in ((model.window_rotary, DEFAULT),
                          (model.full_rotary, YARN)):
        np.testing.assert_allclose(
            plain.rotate(x[0], *plain.rotary_of(group, 16)), rotary(x)[0],
            atol=1e-5)


# --------------------------------------------------------------------------
# the model

def _model_and_params(rows=2, s=32, **over):
    model = MellumTiny(dtype=jnp.float32, **over)
    tokens = np.random.default_rng(0).integers(
        0, 512, (rows, s)).astype(np.int32)
    return model, model.init(jax.random.PRNGKey(0), tokens), tokens


def _plain_loss(params, tokens, kinds, **over):
    return plain.causal_lm_nll(params, tokens, layer_types=kinds,
                               **{**PLAIN, **over}).mean()


@pytest.mark.parametrize("rows", (1, 2))
def test_model_loss_and_gradients_are_the_plain_reference_s(rows):
    """Three windowed layers and a global one, 4 query heads over 2 key
    heads, a window of 8 in 32 rows, YaRN's ramp over pairs 3..6 of the
    head's 8, 2 of 8 experts held at top-2 and no shared expert: loss to
    1e-5, every gradient leaf to 1e-5 of its largest entry."""
    model, params, tokens = _model_and_params(rows)
    assert (model.heads, model.kv_heads, model.window, model.layer_kinds) \
        == (4, 2, 8, ("sliding_attention",) * 3 + ("full_attention",))
    got, grads = jax.jit(jax.value_and_grad(
        lambda p: mellum_loss(model.apply(p, tokens))))(params)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: _plain_loss(p, tokens, model.layer_kinds)))(params)
    assert abs(float(got - want)) <= 1e-5 * float(want)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    assert len(flat) == len(jax.tree_util.tree_leaves(want_grads)) == 43
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want_grads)):
        assert float(jnp.abs(w).max()) > 0, jax.tree_util.keystr(path)
        assert _rel(g, w) <= 1e-5, jax.tree_util.keystr(path)


CONTROLS = {
    "window_minus_1": {"window": 7},
    "window_plus_1": {"window": 9},
    "not_renormalised": {"renormalise": False},
    "yarn_blend_ignored": {"rope_parameters": {
        **ROPE, "full_attention": {**YARN, "factor": 1.0}}},
    "attention_factor_ignored": {"rope_parameters": {
        **ROPE, "full_attention": {**YARN, "attention_factor": 1.0}}},
    "yarn_on_a_windowed_layer": {"rope_parameters": {
        **ROPE, "sliding_attention": YARN}},
    "heads_interleaved": {"interleaved": True},
    "every_layer_global": {"kinds": ("full_attention",) * 4},
    "every_layer_windowed": {"kinds": ("sliding_attention",) * 4},
}


@pytest.mark.parametrize("wrong", sorted(CONTROLS))
def test_the_tolerance_fails_each_wrong_reference(wrong):
    """What 1e-5 of the loss tells apart: a window one key shorter or
    longer, the chosen probabilities not divided by their sum, YaRN's blend
    (factor 16 read as 1) or its attention factor ignored on the global
    layer, YaRN on the windowed layers, query head i reading key head i %
    2, and a layer read as the other kind."""
    model, params, tokens = _model_and_params()
    got = float(mellum_loss(model.apply(params, tokens)))
    over = dict(CONTROLS[wrong])
    kinds = over.pop("kinds", model.layer_kinds)
    assert abs(got - float(_plain_loss(params, tokens, kinds, **over))) \
        > 1e-5 * got


def test_the_tree_has_no_gate_norm_bias_or_shared_expert():
    model, params, _ = _model_and_params()
    p = params["params"]
    for i in range(4):
        attn = p[f"layer_{i}"]["mixer"]["attn"]
        assert sorted(attn) == ["k", "o", "q", "v"]
        assert all(sorted(w) == ["kernel"] for w in attn.values())
        assert (attn["q"]["kernel"].shape, attn["k"]["kernel"].shape) == (
            (64, 64), (64, 32))
        assert sorted(p[f"layer_{i}"]["ffn"]["moe"]) == [
            "down", "gate", "router", "up"]
        assert p[f"layer_{i}"]["ffn"]["moe"]["router"].shape == (64, 8)
    assert p["lm_head"]["kernel"].shape == (64, 512)
    with pytest.raises(ValueError, match="layer_kinds are"):
        _model_and_params(layer_kinds=("full", "window", "window", "full"))
    whole = Mellum2_12B()
    assert len(whole.layer_kinds) == 28
    assert whole.layer_kinds[:4] == ("sliding_attention",) * 3 + (
        "full_attention",)
    assert whole.layer_kinds.count("full_attention") == 7
    assert whole.full_rotary.yarn == (16.0, 8192, 32.0, 1.0, FACTOR)
    assert whole.window_rotary.yarn is None
    assert whole.window_rotary.theta == whole.full_rotary.theta == 500000.0


# --------------------------------------------------------------------------
# the expert layer without a shared expert, and the share

def _layer_operands(E=8, D=32, M=16, T=48):
    rng = np.random.default_rng(1)

    def normal(*shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape).astype(np.float32)
                           * scale)

    return (normal(T, D), normal(D, E, scale=D ** -0.5),
            normal(E, D, M, scale=D ** -0.5), normal(E, D, M, scale=D ** -0.5),
            normal(E, M, D, scale=M ** -0.5))


def test_the_four_shares_parts_add_up_to_the_uncut_layer():
    """The model-configs guide's test: four chips hold two of eight experts
    each and route over all eight; the four parts add up to the uncut
    layer's output of the plain reference (``plain_mellum.experts`` holding
    all eight). No shared expert: nothing is counted twice."""
    E, K, M = 8, 2, 16
    x, wr, wg, wu, wd = _layer_operands()
    total = 0.0
    for first in range(0, E, 2):
        layer = KimiSparseMoe(E, 2, first, K, M, 1.0, 0, jnp.float32,
                              select_bias=False, scoring="softmax")
        held = {name: w[first:first + 2] for name, w in
                (("gate", wg), ("up", wu), ("down", wd))}
        part = layer.apply({"params": {"router": wr, **held}}, x[None])[0]
        total = total + part
        alone = plain.experts(x, {"router": wr, **held}, top_k=K,
                              share=(first, 2), dtype=jnp.float32)
        assert _rel(part, alone) <= 1e-5
    uncut = plain.experts(
        x, {"router": wr, "gate": wg, "up": wu, "down": wd}, top_k=K,
        share=(0, E), dtype=jnp.float32)
    assert _rel(total, uncut) <= 1e-5
    with pytest.raises(ValueError, match="the share names"):
        plain.experts(x, {"router": wr, "gate": wg, "up": wu, "down": wd},
                      top_k=K, share=(0, 2), dtype=jnp.float32)


def test_the_gate_is_a_softmax_over_all_whose_chosen_sum_to_one():
    x, wr, *_ = _layer_operands()
    weights = plain.gate_weights(x, wr, 2)
    assert weights.shape == (48, 8)
    assert ((weights > 0).sum(axis=-1) == 2).all()
    np.testing.assert_allclose(weights.sum(axis=-1), 1.0, rtol=1e-6)
    raw = plain.gate_weights(x, wr, 2, renormalise=False)
    probs = jax.nn.softmax(x @ wr, axis=-1)
    np.testing.assert_allclose(raw.sum(axis=-1),
                               jnp.sort(probs, axis=-1)[:, -2:].sum(axis=-1),
                               rtol=1e-5)
    assert float(raw.sum(axis=-1).max()) < 1.0


@pytest.mark.parametrize("aux", (False, True))
def test_no_shared_expert_builds_no_module_and_adds_nothing(aux):
    """``shared=0``: no ``shared`` entry in the tree, the output is the
    held experts' part alone; ``aux`` still returns the pair."""
    x, wr, wg, wu, wd = _layer_operands()
    layer = KimiSparseMoe(8, 2, 0, 2, 16, 1.0, 0, jnp.float32,
                          select_bias=False, scoring="softmax", aux=aux)
    params = layer.init(jax.random.PRNGKey(0), x[None])
    assert sorted(params["params"]) == ["down", "gate", "router", "up"]
    out = layer.apply({"params": {"router": wr, "gate": wg[:2], "up": wu[:2],
                                  "down": wd[:2]}}, x[None])
    y = out[0] if aux else out
    assert (isinstance(out, tuple) and out[1].shape == ()) == aux
    want = plain.experts(x, {"router": wr, "gate": wg[:2], "up": wu[:2],
                             "down": wd[:2]}, top_k=2, share=(0, 2),
                         dtype=jnp.float32)
    assert _rel(y[0], want) <= 1e-5


TREES_WITH_A_SHARED_EXPERT = {
    # the model, a layer with an expert layer, the shared SwiGLU's width
    "kimi_linear": (KimiLinearTiny, "layer_1", 32),
    "joyai": (JoyAIFlashTiny, "layer_1", 32),
    "laguna": (LagunaTiny, "layer_1", 32),
    "qwen3_next": (Qwen3NextTiny, "layer_0", 32),
}


@pytest.mark.parametrize("name", sorted(TREES_WITH_A_SHARED_EXPERT))
def test_a_model_with_a_shared_expert_keeps_its_tree(name):
    tiny, layer, width = TREES_WITH_A_SHARED_EXPERT[name]
    model = tiny()
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            np.zeros((1, 32), np.int32))
    moe = shapes["params"][layer]["ffn"]["moe"]
    assert sorted(moe["shared"]) == ["down", "gate", "up"]
    assert moe["shared"]["gate"]["kernel"].shape[1] == width


@pytest.mark.parametrize("t, top_k, held, e, rungs", [
    # the deployment's 4 x 8,192 tokens, then this cell's 2 x 8,192: half of
    # all rows is the bound, and 5/8 of it the rung under it
    (32768, 8, 16, 64, (81920, 131072)),
    (16384, 8, 16, 64, (40960, 65536)),
    # Qwen3-Next's: the rung saves 7,680 rows, the least that gets one
    (16384, 10, 32, 512, (12800, 20480)),
    # the six other shares that run: the rung would save 1,536 (Kimi-Linear
    # and JoyAI), 3,072 (Laguna), 6,144 (Keye, ZAYA1: every row is the bound,
    # and twice the even part all the same) and 4,608 rows (Nemotron), under
    # HELD_RUNG_MIN_SAVED: the bound alone
    (8192, 8, 8, 256, (4096,)),
    (8192, 8, 16, 256, (8192,)),
    (8192, 8, 16, 128, (16384,)),
    (16384, 1, 8, 16, (16384,)),
    (16384, 6, 8, 128, (12288,)),
    # a saving of 6,656 rows, one row multiple under the floor, and of 7,168
    (32768, 1, 7, 26, (17920,)),
    (32768, 1, 7, 24, (12288, 19456)),
    # three quarters of the experts held: the even part is over 5/8 of all
    # rows, so no rung is left between it and the bound
    (65536, 1, 12, 16, (65536,)),
    # these tests' sizes
    (512, 2, 2, 8, (512,)),
])
def test_held_row_bound_by_hand(t, top_k, held, e, rungs):
    """Twice the even part T k H / E, in 512s, at most all T k: half of all
    rows for a quarter of the experts. The rungs under it by hand: eighths
    of the bound in 512s, none under the even part, the bound last, and the
    bound alone where the rung would save too little."""
    assert held_row_bound(t, top_k, held, e) == rungs[-1] <= t * top_k
    assert held_row_rungs(t, top_k, held, e) == rungs
    assert len(rungs) <= 2 and list(rungs) == sorted(set(rungs))
    assert all(rung % moe_lib.HELD_ROWS_MULTIPLE == 0 for rung in rungs)
    assert all(rung * e >= t * top_k * held for rung in rungs)
    assert len(rungs) == 1 or (rungs[-1] - rungs[0]
                               >= moe_lib.HELD_RUNG_MIN_SAVED)


# T tokens at top-1 over 16 experts, experts 4..7 held: an even part of
# 16,384 rows and a rung of 20,480 under a bound of 32,768
LADDER = dict(t=65536, e=16, held=4, first=4, d=8, m=8)


def _ladder_layer(held_rows, rungs=None):
    """The layer's output and its five gradients (x, logits, gate, up,
    down) in float32 with exactly ``held_rows`` of the tokens routed to the
    held experts by their logits; under ``rungs`` where given, else under
    the ladder the shapes have."""
    t, e, held, first, d, m = (LADDER[k] for k in
                               ("t", "e", "held", "first", "d", "m"))
    rng = np.random.default_rng(held_rows)
    normal = lambda *shape, scale=1.0: jnp.asarray(  # noqa: E731
        rng.normal(size=shape) * scale, jnp.float32)
    x, cot = normal(t, d), normal(t, d)
    weights = (normal(held, d, m, scale=d ** -0.5),
               normal(held, d, m, scale=d ** -0.5),
               normal(held, m, d, scale=m ** -0.5))
    elsewhere = [i for i in range(e) if not first <= i < first + held]
    expert = np.concatenate([
        first + np.arange(held_rows) % held,
        np.asarray(elsewhere)[np.arange(t - held_rows) % len(elsewhere)]])
    rng.shuffle(expert)
    logits = rng.normal(size=(t, e)).astype(np.float32)
    logits[np.arange(t), expert] += 8.0
    logits = jnp.asarray(logits)

    def layer(x, logits, *weights):
        y, _, _, counts = dropless_moe_ffn(
            x, None, *weights, top_k=1, dtype=jnp.float32,
            first_expert=first, logits=logits)
        return (y * cot).sum(), (y, counts)

    with pytest.MonkeyPatch.context() as patch:
        if rungs is not None:
            patch.setattr(moe_lib, "held_row_rungs", lambda *shape: rungs)
        (_, (y, counts)), grads = jax.value_and_grad(
            layer, argnums=range(5), has_aux=True)(x, logits, *weights)
    assert int(counts[first:first + held].sum()) == held_rows
    return (y, *grads)


@pytest.mark.parametrize("held_rows", (
    0, 16384,                   # none, the even part: the lower rung
    20479, 20480, 20481,        # the lower rung's edge
    32767, 32768, 32769,        # the bound's: beyond it the loop
    49152, 65536,               # a pass and a half of the loop; every row
))
def test_every_rung_and_the_loop_give_the_one_pass_result(held_rows):
    """Whichever rung the held experts' count picks, at each rung's edge, a
    row under and a row over it, and in the loop beyond the bound: output
    and all five gradients are those of one pass over every row, the code
    path there was before there were rungs, to 1e-6."""
    assert held_row_rungs(LADDER["t"], 1, LADDER["held"], LADDER["e"]) == (
        20480, 32768)
    got = _ladder_layer(held_rows)
    want = _ladder_layer(held_rows, rungs=(LADDER["t"],))
    for name, a, b in zip(("y", "x", "logits", "gate", "up", "down"),
                          got, want):
        assert _rel(a, b) <= 1e-6, name
    assert float(jnp.abs(want[0]).max()) > 0 or held_rows == 0


def test_the_probe_says_how_many_layers_take_one_pass():
    """``publish_moe_stats(held=)``: a layer whose held assignments are
    over twice their even part takes more passes than one."""
    even = np.full(8, 64, np.int64)
    skewed = np.array([200, 200, 16, 16, 16, 16, 24, 24], np.int64)
    assert held_row_bound(512, 1, 2, 8) == 512      # the multiple of 512
    out = publish_moe_stats({"a": even, "b": skewed}, held=(0, 2))
    assert out["bps_moe_compact_share"] == 1.0
    out = publish_moe_stats({"a": even * 64, "b": skewed * 64}, held=(0, 2))
    assert held_row_bound(512 * 64, 1, 2, 8) == 16384 < 400 * 64
    assert out["bps_moe_compact_share"] == 0.5
    assert abs(out["bps_moe_held_load"]
               - (128 + 400) / (2 * 512 * 2 / 8)) < 1e-9


@pytest.mark.parametrize("held_rows, share", [
    ((16384,), 0.625), ((20480,), 0.625),       # the lower rung, to its edge
    ((20481,), 1.0), ((32768,), 1.0),           # the bound
    ((32769,), 2.0), ((65536,), 2.0),           # the loop: two passes' rows
    ((16384, 18000, 22000, 40000), (0.625 + 0.625 + 1.0 + 2.0) / 4),
])
def test_the_probe_says_which_rung_each_layer_takes(held_rows, share):
    """``bps_moe_pass_rows_share``: the rows of the pass a layer's count
    picks over the bound, averaged over layers, from made-up counts of
    65,536 assignments over 16 experts with experts 4..7 held (a rung of
    20,480 under a bound of 32,768)."""
    def layer(rows):
        counts = np.zeros(16, np.int64)
        counts[4], counts[6], counts[12] = rows - rows // 3, rows // 3, \
            65536 - rows
        return counts

    out = publish_moe_stats([layer(rows) for rows in held_rows], held=(4, 4))
    assert out["bps_moe_pass_rows_share"] == pytest.approx(share)
    assert metrics._py_gauges["bps_moe_pass_rows_share"] == \
        out["bps_moe_pass_rows_share"]
    assert out["bps_moe_compact_share"] == pytest.approx(
        np.mean([rows <= 32768 for rows in held_rows]))
    # a shape with one rung reads what it did before there were rungs
    small = np.array([100, 100, 28, 28, 64, 64, 64, 64], np.int64)
    assert publish_moe_stats([small], held=(0, 2))[
        "bps_moe_pass_rows_share"] == 1.0
    assert "bps_moe_pass_rows_share" not in publish_moe_stats([small])


# --------------------------------------------------------------------------
# the configuration's arithmetic

def _config():
    return (cell_lib.load_json(CONFIG + ".json"),
            cell_lib.load_module(CONFIG + ".py", "mellum_config"))


def test_parameter_count_by_hand():
    """The docstring of the configuration's ``.py``, and the published
    model's 12B-A2.5B."""
    attention = 2 * 2304 * 4096 + 2 * 2304 * 512
    expert, router, norms = 3 * 2304 * 896, 2304 * 64, 2 * 2304
    assert (attention, expert, router, norms) == (
        21_233_664, 6_193_152, 147_456, 4_608)
    layer = attention + norms + router + 16 * expert
    ends = 2 * 24_576 * 2304 + 2304
    assert (layer, ends) == (120_476_160, 113_248_512)
    cfg, config = _config()
    assert cfg["n_params"] == 4 * layer + ends == 595_153_152
    init, _ = config.build(cfg)
    shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
    assert sum(math.prod(x.shape)
               for x in jax.tree_util.tree_leaves(shapes)) == cfg["n_params"]
    whole = attention + norms + router + 64 * expert
    assert whole == 417_747_456
    published = 28 * whole + 2 * 98_304 * 2304 + 2304
    active = 28 * (attention + norms + router + 8 * expert) \
        + 2 * 98_304 * 2304 + 2304
    assert (published, active) == (12_149_915_904, 2_439_053_568)
    assert cfg["published"]["n_params"] == published
    assert cfg["published"]["n_active_params"] == active
    # every width is the published one; the two lists stand whole
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["sliding_window"],
            cfg["moe_intermediate_size"], cfg["intermediate_size"],
            cfg["num_experts"], cfg["num_experts_per_tok"]) == (
        2304, 128, 32, 4, 1024, 896, 7168, 64, 8)
    assert len(cfg["layer_types"]) == len(cfg["mlp_layer_types"]) == 28
    assert sorted(r.split()[0] for r in cfg["reduced"]) == [
        "num_hidden_layers", "num_local_experts", "vocab_size"]


def test_flops_per_token_by_hand():
    """Needed pairs only: the band in the three windowed layers, the causal
    triangle in the global one; two of a token's eight experts held."""
    cfg, config = _config()
    s = 8_192
    triangle, band = s * (s + 1) // 2, 1024 * s - 523_776
    assert (triangle, band) == (33_558_528, 7_864_832)
    assert band == sum(min(q + 1, 1024) for q in range(s))
    assert (config.needed_pairs(s), config.needed_pairs(s, 1024)) == (
        triangle, band)
    pairs = 32 * 1536 * (triangle + 3 * band)
    assert round(32 * 1536 * triangle / 1e12, 3) == 1.649
    assert round(3 * 32 * 1536 * band / 1e12, 3) == 1.160
    row = 6 * 4 * (21_233_664 + 147_456 + 2 * 6_193_152)
    assert row == 810_418_176
    head = 6 * 2304 * 24_576
    assert config.flops_per_token(cfg) == (
        s * row + pairs + (s - 1) * head) // s == 1_493_033_472
    assert round(100 * pairs / (s * 1_493_033_472)) == 23


@pytest.mark.parametrize("windowed, least_ms", [(True, 11.774),
                                                (False, 16.746)])
def test_the_attention_roofline_by_hand(windowed, least_ms):
    """``layers/mswa.py``: the band's pairs of three layers, the triangle's
    of one, at the cell's two sequences, against 197 TFLOP/s."""
    cfg, _ = _config()
    reader = cell_lib.load_module(
        os.path.join(REPO, "benchmark", "layers", "mswa.py"), "mswa_reader")
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    pct = reader.roofline_pct(100.0, cfg, 2, peaks, windowed)
    assert abs(pct - least_ms) < 1e-3 * least_ms, pct
    assert reader.LAYER == "windowed and global attention"


# --------------------------------------------------------------------------
# tracing, and the normal path

def test_scopes_nest_in_the_compiled_program():
    """``bps.swa.window`` and ``bps.swa.full`` enclose ``full_attention``'s
    own scope, ``bps.swa.proj`` the projections, forward and backward; the
    share keeps ``bps.moe.route`` / ``bps.moe.experts``; there is no
    ``bps.moe.shared``."""
    model, params, tokens = _model_and_params(1)
    names = set(re.findall(r'op_name="([^"]*)"', jax.jit(jax.value_and_grad(
        lambda p: mellum_loss(model.apply(p, tokens)))).lower(
            params).compile().as_text()))
    for scope in ("bps.swa.window", "bps.swa.full", "bps.swa.proj",
                  "bps.moe.route", "bps.moe.experts", "bps.lm.head"):
        for pass_ in ("/jvp(", "/transpose(jvp("):
            assert any((f"/{scope}/" in n or f"({scope})" in n) and pass_ in n
                       for n in names), (scope, pass_)
    for outer in ("bps.swa.window", "bps.swa.full"):
        assert any(f"/{outer}/bps.attn.xla/" in n for n in names), outer
    assert not any("bps.attn.xla" in n and "bps.swa." not in n
                   for n in names)
    assert not any("bps.moe.shared" in n for n in names)


def test_the_counters_at_trace_time():
    """One trace of the loss: 4 mixers, 4 attention sites of which 3 under
    a window (on the CPU the XLA form: the square over the band's pairs)."""
    model, params, tokens = _model_and_params(2, 32)
    names = (MELLUM_SITES, XLA_SITES, KERNEL_SITES, WINDOW_SITES,
             WINDOW_WALKED, WINDOW_NEEDED)
    before = [metrics.counter(n) for n in names]
    jax.jit(lambda p: mellum_loss(model.apply(p, tokens))).lower(params)
    got = [metrics.counter(n) - b for n, b in zip(names, before)]
    needed = 3 * 2 * 4 * (8 * 32 - 28)
    assert got == [4, 4, 0, 3, 3 * 2 * 4 * 32 * 32, needed]


@pytest.mark.parametrize("window, blocks, walked", [
    # a band shorter than the causal block: capped, Laguna's point (PR 47)
    (512, (512, 512), 31 * 512 ** 2), (1023, (512, 512), 45 * 512 ** 2),
    # a band of a whole block or more: the causal blocks, this cell's
    (1024, (1024, 1024), 15 * 1024 ** 2),
    (2048, (1024, 1024), 21 * 1024 ** 2),
    (None, (1024, 1024), None)])
def test_the_blocks_under_a_window_by_hand(window, blocks, walked):
    """``_blocks`` at the cell's call (s 8,192, heads 128 wide): one rule of
    the shapes with two measured points (its docstring). 1024 x 1024 walks
    two blocks a query block (one in the first), 15 in all, for a band of
    7,864,832 pairs: ``mswa.walked_pairs_ratio`` 2.0."""
    fa = importlib.import_module("byteps_tpu.ops.flash_attention")
    assert fa._blocks(8192, 8192, 128, window) == blocks
    if window is not None:
        assert fa.window_walked_pairs(8192, 8192, 128, window) == walked
    if window == 1024:
        assert round(walked / (1024 * 8192 - 523_776), 4) == 1.9999


def test_stats_are_sown_only_when_asked_for():
    model, params, tokens = _model_and_params()
    nll, stats = model.apply(params, tokens, mutable=["moe_stats"])
    assert nll.shape == (2, 31)
    counts = jax.tree_util.tree_leaves(stats["moe_stats"])
    assert len(counts) == 4 and all(int(c.sum()) == 2 * 32 * 2
                                    for c in counts)
    assert model.apply(params, tokens).shape == (2, 31)


def test_the_model_trains_through_make_train_step_on_the_mesh():
    """bps.init() -> make_train_step(loss_fn, adamw) -> step on 8 virtual
    chips: the first loss is the single-device loss of the same batch and
    the loss falls."""
    import byteps_tpu.jax as bps
    from byteps_tpu.jax.training import (make_train_step, replicate,
                                         shard_batch)

    model, params, tokens = _model_and_params(8, 32)

    def loss_fn(p, batch):
        return mellum_loss(model.apply(p, batch["tokens"]))

    one = jax.jit(loss_fn)
    alone = float(np.mean([one(params, {"tokens": tokens[i:i + 1]})
                           for i in range(8)]))
    bps.init()
    tx = optax.adamw(1e-2)
    step = make_train_step(loss_fn, tx)
    state = (replicate(params), replicate(tx.init(params)))
    losses = []
    for _ in range(3):
        *state, loss = step(*state, shard_batch({"tokens": tokens}))
        losses.append(float(loss))
    assert abs(losses[0] - alone) <= 1e-5 * alone
    assert losses[-1] < losses[0] - 0.1


def test_the_reference_imports_nothing_of_the_program():
    source = open(plain.__file__).read()
    assert "byteps_tpu" not in source.split('"""', 2)[2]
    assert importlib.import_module("benchmark.lib.plain_mellum") is plain


# sha256 of the lowered gradient of the tiny model's loss as PR 58 left it:
# a later change to ``KimiSparseMoe``, ``KimiBlock``, ``Rotary`` or the
# share's pass that is not meant to move this cell's step leaves it
PINNED_AT_PR_58 = ("4e8144d048ef135e50e951e009982fa23617902f660e9b4f9780504"
                   "279f3962f")


def test_the_cell_s_step_lowers_to_what_it_did():
    model, tokens = MellumTiny(), np.zeros((2, 32), np.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    with jax.default_matmul_precision(None):       # not this file's fixture
        text = jax.jit(jax.grad(lambda p: mellum_loss(
            model.apply(p, tokens)))).lower(params).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PINNED_AT_PR_58

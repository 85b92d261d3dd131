"""LagunaModel and what it brought (tier-1, CPU, float32, seeded): a window
and grouped keys through ``full_attention`` in both of its forms, a partial
rotary with YaRN's frequencies in ``_rope``, mixers and head counts from
per-layer lists, a per-head output gate, an expert share without a
selection bias.

The yardstick shares no code with the program: ``benchmark/lib/
plain_laguna.py`` (the rotation as a complex multiplication over the
rotated part, YaRN's ramp in numpy, attention in query blocks over all keys
with the band as a mask and the group as an axis, a literal ``argsort``
gate over dense experts). In float32 on the CPU both sides differ by the
order sums are taken in: 1e-5 of the loss and of a gradient leaf's largest
entry (measured: 1e-6 and 5e-8); nothing discrete can flip at these sizes
and seeds. The cases at the end of each section show what that tolerance
fails.
"""

import contextlib
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

from byteps_tpu.models import LagunaTiny, LagunaXS2, laguna_loss
from byteps_tpu.models.kimi_linear import KimiSparseMoe
from byteps_tpu.models.laguna import Rotary
from byteps_tpu.models.llama import _rope, yarn_inv_freq, yarn_ramp
from byteps_tpu.monitor import metrics
from byteps_tpu.parallel.ring_attention import (
    KERNEL_SITES, WINDOW_NEEDED, WINDOW_SITES, WINDOW_WALKED, XLA_SITES,
    attention_form, full_attention)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.lib import cell as cell_lib  # noqa: E402
from benchmark.lib import plain_kimi_linear as plain_kimi  # noqa: E402
from benchmark.lib import plain_laguna as plain  # noqa: E402

ra = importlib.import_module("byteps_tpu.parallel.ring_attention")
CONFIG = os.path.join(REPO, "benchmark", "configs", "laguna-xs.2")
FACTOR = 1.4158883083359672
ROPE = {
    "full_attention": {
        "rope_theta": 500000.0, "rope_type": "yarn", "factor": 64.0,
        "original_max_position_embeddings": 16, "beta_slow": 1.0,
        "beta_fast": 64.0, "attention_factor": FACTOR,
        "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000.0,
                          "partial_rotary_factor": 1}}
PLAIN = dict(head_dim=16, window=8, rope_parameters=ROPE, top_k=2,
             first_expert=0, routed_scale=2.5, eps=1e-6, dtype=jnp.float32,
             query_block=8, head_rows=32)


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

def test_yarn_numbers_by_hand():
    """The global layers' 64 rotated entries: r(beta) = 64 ln(4096 / (2 pi
    beta)) / (2 ln 500000); r(64) = 5.66 and r(1) = 15.80, so the ramp runs
    from pair 5 to pair 16: pairs 0..5 keep their own frequency, pairs
    16..31 are divided by 64, pair 10 is 5/11 of the way."""
    two_ln = 2 * math.log(500000.0)
    assert round(64 * math.log(4096 / (2 * math.pi * 64)) / two_ln, 2) == 5.66
    assert round(64 * math.log(4096 / (2 * math.pi)) / two_ln, 2) == 15.80
    assert yarn_ramp(64, 500000.0, 4096, 64.0, 1.0) == (5, 16)
    w = np.asarray(yarn_inv_freq(64, 500000.0, 64.0, 4096, 64.0, 1.0))
    own = 500000.0 ** (-np.arange(32) / 32.0)
    assert w.shape == (32,) and w[0] == 1.0
    np.testing.assert_allclose(w[:6], own[:6], rtol=1e-6)
    np.testing.assert_allclose(w[16:], own[16:] / 64, rtol=1e-6)
    np.testing.assert_allclose(w[31], 500000.0 ** (-31 / 32) / 64, rtol=1e-6)
    assert abs(w[31] - 4.7092e-8) < 1e-11          # 3.0139e-6 / 64
    np.testing.assert_allclose(
        w[10], own[10] * (6 / 11 + 5 / 11 / 64), rtol=1e-6)
    # both ends clamp to 0 .. rotary - 1
    assert yarn_ramp(8, 500000.0, 16, 64.0, 1.0) == (0, 1)
    assert yarn_ramp(64, 10000.0, 10 ** 9, 1.0, 1e-9)[1] == 63
    # the attention factor is the source's own: 0.1 ln(64) + 1
    assert abs(0.1 * math.log(64.0) + 1 - FACTOR) < 1e-12
    # the plain reference's ramp, written apart, is the same
    np.testing.assert_allclose(
        plain.yarn_frequencies(64, 500000.0, 64.0, 4096, 64.0, 1.0), w,
        rtol=1e-6)


def test_a_partial_rotation_turns_the_first_entries_and_passes_the_rest():
    """``rotary_dim`` 8 of 16: entries (j, j + 4) of the first 8 turn by pos
    x w_j, times the factor; entries 8..15 pass bit for bit; a logit's
    rotated part carries the factor's square."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((1, 12, 3, 16)), jnp.float32)
    positions = jnp.arange(12)[None]
    w = yarn_inv_freq(8, 500000.0, 64.0, 16, 64.0, 1.0)
    got = _rope(x, positions, 500000.0, rotary_dim=8, inv_freq=w,
                factor=FACTOR)
    assert jnp.array_equal(got[..., 8:], x[..., 8:])
    angle = np.arange(12)[:, None] * np.asarray(w)[None]      # [s, 4]
    z = (np.asarray(x[0, :, :, :4]) + 1j * np.asarray(x[0, :, :, 4:8])) \
        * (FACTOR * np.exp(1j * angle))[:, None, :]
    np.testing.assert_allclose(got[0, :, :, :4], z.real, atol=1e-5)
    np.testing.assert_allclose(got[0, :, :, 4:8], z.imag, atol=1e-5)
    # a rotation: the turned part's norm is the factor times the old one
    np.testing.assert_allclose(
        jnp.linalg.norm(got[..., :8], axis=-1),
        FACTOR * jnp.linalg.norm(x[..., :8], axis=-1), rtol=1e-5)
    # and the plain reference's complex multiplication says the same
    np.testing.assert_allclose(
        plain.rotate(x[0], 8, np.asarray(w), FACTOR), got[0], atol=1e-5)
    whole = Rotary(10000.0)(x)
    np.testing.assert_allclose(whole, _rope(x, positions, 10000.0),
                               atol=0)


def test_rope_defaults_lower_to_what_they_did():
    """OLMoE's, Keye's and JoyAI's calls: no new argument, the same text."""
    x = jax.ShapeDtypeStruct((1, 8, 2, 16), jnp.float32)
    p = jax.ShapeDtypeStruct((1, 8), jnp.int32)

    def before(x, positions, theta=10000.0):
        half = x.shape[-1] // 2
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
        angles = positions[..., None].astype(jnp.float32) * freqs
        cos = jnp.cos(angles)[:, :, None, :]
        sin = jnp.sin(angles)[:, :, None, :]
        x1, x2 = x[..., :half].astype(jnp.float32), \
            x[..., half:].astype(jnp.float32)
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                               axis=-1).astype(x.dtype)

    assert (jax.jit(lambda x, p: _rope(x, p)).lower(x, p).as_text()
            == jax.jit(lambda x, p: before(x, p)).lower(x, p).as_text())
    assert (jax.jit(lambda x, p: _rope(x, p, rotary_dim=16, factor=1.0))
            .lower(x, p).as_text()
            == jax.jit(lambda x, p: before(x, p)).lower(x, p).as_text())


# --------------------------------------------------------------------------
# the window and the grouped keys, in both forms

def _naive(q, k, v, window):
    """Every (query, key) pair written out: query head i over key head i //
    groups, the band as a mask, softmax in float32."""
    groups = q.shape[2] // k.shape[2]
    s = q.shape[1]
    out = np.zeros(q.shape, np.float32)
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    for head in range(q.shape[2]):
        logits = q[0, :, head] @ k[0, :, head // groups].T \
            * q.shape[-1] ** -0.5
        back = np.arange(s)[:, None] - np.arange(s)[None, :]
        seen = (back >= 0) & (back < (s if window is None else window))
        probs = np.where(seen, np.exp(logits - logits.max(-1, keepdims=True)),
                         0.0)
        out[0, :, head] = (probs / probs.sum(-1, keepdims=True)) \
            @ v[0, :, head // groups]
    return out


@contextlib.contextmanager
def _kernel_form(monkeypatch, blocks=(32, 64)):
    """Steer ``full_attention`` to the kernel off the chip
    (``tests/test_attention_form.py``): the rule reads a ``tpu`` backend and
    bf16, the kernel itself still sees the CPU and interprets."""
    fa = importlib.import_module("byteps_tpu.ops.flash_attention")
    with monkeypatch.context() as m:
        m.setattr(ra, "attention_form", lambda *a, **k: "kernel")
        m.setattr(fa, "_blocks", lambda s_q, s_k, d, window=None: blocks)
        yield


WINDOW_CASES = {
    # s, query heads, key heads, window
    "short_window": (96, 4, 4, 24),
    "window_past_the_sequence": (96, 4, 4, 200),
    "ragged_sequence": (100, 2, 2, 17),
    "grouped_keys": (96, 6, 2, 24),
    "grouped_causal": (96, 6, 2, None),
    "grouped_ragged": (75, 4, 1, 40),
}


@pytest.mark.parametrize("case", sorted(WINDOW_CASES))
def test_both_forms_are_the_naive_band(rng, monkeypatch, case):
    """``full_attention(window=w)``: the XLA form, the interpreted kernel
    (blocks of 32 x 64: the band crosses blocks, a sequence that is no
    multiple of either is padded) and a naive banded softmax agree, values
    and gradients; a window past the sequence is the causal triangle."""
    s, heads, kv_heads, window = WINDOW_CASES[case]
    q = jnp.asarray(rng.standard_normal((1, s, heads, 16)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((1, s, kv_heads, 16)),
                        jnp.float32) for _ in range(2))
    w = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)

    def run():
        return jax.value_and_grad(
            lambda q, k, v: (full_attention(q, k, v, causal=True,
                                            window=window) * w).sum(),
            argnums=(0, 1, 2))(q, k, v)

    want = _naive(q, k, v, window)
    xla_sites = metrics.counter(XLA_SITES)
    xla = run()
    assert metrics.counter(XLA_SITES) == xla_sites + 1
    np.testing.assert_allclose(
        full_attention(q, k, v, causal=True, window=window), want,
        atol=2e-5)
    kernel_sites = metrics.counter(KERNEL_SITES)
    with _kernel_form(monkeypatch):
        kernel = run()
        np.testing.assert_allclose(
            full_attention(q, k, v, causal=True, window=window), want,
            atol=2e-5)
    assert metrics.counter(KERNEL_SITES) == kernel_sites + 2
    assert abs(float(xla[0] - kernel[0])) <= 1e-4 * abs(float(xla[0])) + 1e-4
    for a, b in zip(xla[1], kernel[1]):
        assert a.shape == b.shape and _rel(b, a) <= 2e-5
    if window is not None and window >= s:
        causal = jax.value_and_grad(
            lambda q, k, v: (full_attention(q, k, v, causal=True) * w).sum(),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(xla[1], causal[1]):
            assert _rel(a, b) <= 1e-6


def test_a_window_needs_a_causal_mask_and_heads_that_divide(rng):
    x = jnp.zeros((1, 16, 4, 8), jnp.float32)
    with pytest.raises(ValueError, match="causal"):
        full_attention(x, x, x, causal=False, window=4)
    with pytest.raises(ValueError, match="query heads"):
        full_attention(x, x[:, :, :3], x[:, :, :3], causal=True)


@pytest.mark.parametrize("args, form", [
    # the cell's two kinds of layer: s 8192, 128-wide heads, bf16
    (("tpu", 8192, 8192, 128, True, jnp.bfloat16, 128, 512), "kernel"),
    (("tpu", 8192, 8192, 128, True, jnp.bfloat16, 128, None), "kernel"),
    (("tpu", 256, 256, 128, True, jnp.bfloat16, 128, 64), "xla"),
    (("tpu", 8192, 8192, 128, True, jnp.float32, 128, 512), "xla"),
    (("cpu", 8192, 8192, 128, True, jnp.bfloat16, 128, 512), "xla"),
])
def test_the_rule_with_a_window(args, form):
    """A window does not move the rule; without a causal mask it is an
    error, whatever the backend."""
    assert attention_form(*args) == form
    with pytest.raises(ValueError, match="causal"):
        attention_form("tpu", 8192, 8192, 128, False, jnp.bfloat16, 128, 512)


def test_walked_and_needed_pairs_by_hand(monkeypatch):
    """The cell's windowed call, s 8192, window 512: the band holds 512 x
    8192 - 130,816 pairs. Blocks of 512 x 512 (the call's own, from
    ``_blocks``) touch two blocks a q block (one in the first): 31 blocks,
    2.0 x the band; 1024 x 1024 15 blocks, 3.9 x; 256 x 512 1.5 x. The count
    is the kernels' own: the list whose largest entry sizes their grids."""
    fa = importlib.import_module("byteps_tpu.ops.flash_attention")
    needed = 512 * 8192 - 130_816
    assert needed == 4_063_488 == sum(min(q + 1, 512) for q in range(8192))
    assert fa._blocks(8192, 8192, 128, 512) == (512, 512)
    assert fa.window_walked_pairs(8192, 8192, 128, 512) == 31 * 512 ** 2

    def walked(s, window, blocks):
        monkeypatch.setattr(fa, "_blocks", lambda *a, **k: blocks)
        return fa.window_walked_pairs(s, s, 128, window)

    assert walked(8192, 512, (1024, 1024)) == 15 * 1024 ** 2
    # the first two q blocks of 256 see key block 0 alone, the 30 after two
    assert walked(8192, 512, (256, 512)) == 62 * 256 * 512
    assert walked(8192, 512, (8192, 8192)) == 8192 ** 2
    assert walked(32, 8, (64, 64)) == 1024          # blocks clamp to s
    for blocks, nq, nk in (((512, 512), 16, 16), ((256, 512), 32, 16),
                           ((1024, 1024), 8, 8)):
        per_q = fa._window_k_blocks(512, *blocks, nq, nk)
        assert fa._window_live_blocks(512, *blocks, nq, nk) == max(per_q) == 2
        assert sum(per_q) * blocks[0] * blocks[1] == walked(8192, 512, blocks)


def test_the_three_window_counters(rng, monkeypatch):
    """Bumped while tracing, once a windowed call site: sites by 1, walked
    and needed pairs by batch x heads x one head's; a call without a window
    bumps none; the kernel form walks its blocks, the XLA form the square."""
    q = jnp.asarray(rng.standard_normal((2, 64, 6, 16)), jnp.float32)
    k = v = jnp.asarray(rng.standard_normal((2, 64, 2, 16)), jnp.float32)

    def counters():
        return tuple(metrics.counter(n) for n in (WINDOW_SITES, WINDOW_WALKED,
                                                  WINDOW_NEEDED))

    before = counters()
    f = jax.jit(lambda q, k, v: full_attention(q, k, v, causal=True,
                                               window=8))
    f(q, k, v)
    f(q, k, v)
    needed = 12 * (8 * 64 - 28)
    assert counters() == (before[0] + 1, before[1] + 12 * 64 * 64,
                          before[2] + needed)
    before = counters()
    with _kernel_form(monkeypatch, blocks=(16, 16)):
        jax.jit(lambda q, k, v: full_attention(
            q, k, v, causal=True, window=8)).lower(q, k, v)
    # q blocks of 16 see their own key block and the one before it
    assert counters() == (before[0] + 1, before[1] + 12 * 7 * 256,
                          before[2] + needed)
    before = counters()
    # a window past the sequence needs the causal triangle
    jax.jit(lambda q, k, v: full_attention(
        q, k, v, causal=True, window=100)).lower(q, k, v)
    assert counters() == (before[0] + 1, before[1] + 12 * 64 * 64,
                          before[2] + 12 * 64 * 65 // 2)
    before = counters()
    jax.jit(lambda q, k, v: full_attention(q, k, v, causal=True)).lower(
        q, k, v)
    assert counters() == before


# --------------------------------------------------------------------------
# the model

def _model_and_params(rows=2, s=32, **over):
    model = LagunaTiny(dtype=jnp.float32, **over)
    tokens = np.random.default_rng(0).integers(
        0, 512, (rows, s)).astype(np.int32)
    return model, model.init(jax.random.PRNGKey(0), tokens), tokens


def _plain_loss(params, tokens, kinds, **over):
    return plain.causal_lm_nll(params, tokens, layer_types=kinds,
                               **{**PLAIN, **over}).mean()


@pytest.mark.parametrize("rows", (1, 2))
def test_model_loss_and_gradients_are_the_plain_reference_s(rows):
    """A global layer with the dense SwiGLU, two windowed expert layers, a
    global expert layer; 4 and 6 query heads over 2 key heads; a window of 8
    in 32 rows; a rotary over half a head with YaRN's ramp inside it (lo 0,
    hi 2): loss to 1e-5, every gradient leaf to 1e-5 of its largest entry."""
    model, params, tokens = _model_and_params(rows)
    assert (model.layer_heads, model.kv_heads, model.window) == (
        (4, 6, 6, 4), 2, 8)
    got, grads = jax.jit(jax.value_and_grad(
        lambda p: laguna_loss(model.apply(p, tokens))))(params)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: _plain_loss(p, tokens, model.layer_kinds)))(params)
    assert abs(float(got - want)) <= 1e-5 * float(want)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    assert len(flat) == len(jax.tree_util.tree_leaves(want_grads)) > 40
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want_grads)):
        assert float(jnp.abs(w).max()) > 0, jax.tree_util.keystr(path)
        assert _rel(g, w) <= 1e-5, jax.tree_util.keystr(path)


@pytest.mark.parametrize("wrong", [
    {"window": 7}, {"window": 9}, {"logits_dtype": jnp.bfloat16},
    {"kinds": ("full_attention",) * 4},
    {"rope": "no_factor"}, {"rope": "whole_head"}])
def test_the_tolerance_fails_another_band_precision_or_rotation(wrong):
    """What 1e-5 of the loss tells apart: a window one key shorter or
    longer, bf16 logits and statistics, a windowed layer read as global, a
    rotation without its attention factor or over the whole head."""
    model, params, tokens = _model_and_params()
    got = float(laguna_loss(model.apply(params, tokens)))
    kinds, over = model.layer_kinds, dict(wrong)
    if "kinds" in over:
        kinds = over.pop("kinds")
    if over.get("rope") == "no_factor":
        over["rope_parameters"] = {**ROPE, "full_attention": {
            **ROPE["full_attention"], "attention_factor": 1.0}}
    elif over.get("rope") == "whole_head":
        over["rope_parameters"] = {**ROPE, "full_attention": {
            **ROPE["full_attention"], "partial_rotary_factor": 1}}
    over.pop("rope", None)
    assert abs(got - float(_plain_loss(params, tokens, kinds, **over))) \
        > 1e-5 * got


def test_heads_and_kinds_come_from_the_per_layer_lists():
    model, params, _ = _model_and_params()
    p = params["params"]
    assert [p[f"layer_{i}"]["mixer"]["attn"]["q"]["kernel"].shape[1]
            for i in range(4)] == [64, 96, 96, 64]
    assert [p[f"layer_{i}"]["mixer"]["attn"]["gate"]["kernel"].shape[1]
            for i in range(4)] == [4, 6, 6, 4]
    assert all(p[f"layer_{i}"]["mixer"]["attn"]["k"]["kernel"].shape[1] == 32
               for i in range(4))
    assert "mlp" in p["layer_0"]["ffn"] and "moe" in p["layer_3"]["ffn"]
    assert "select_bias" not in p["layer_1"]["ffn"]["moe"]
    with pytest.raises(ValueError, match="one entry a layer"):
        _model_and_params(layer_heads=(4, 6))
    with pytest.raises(ValueError, match="layer_kinds are"):
        _model_and_params(layer_kinds=("full", "window", "window", "full"))
    xs2 = LagunaXS2()
    assert (len(xs2.layer_kinds), xs2.layer_kinds[:5], xs2.layer_heads[:5],
            xs2.layer_ffn[:2]) == (
        40, ("full_attention",) + ("sliding_attention",) * 3
        + ("full_attention",), (48, 64, 64, 64, 48), ("dense", "sparse"))
    assert xs2.layer_kinds.count("full_attention") == 10


# --------------------------------------------------------------------------
# the share

def test_the_shares_parts_add_up_with_the_shared_expert_counted_once():
    """The model-configs guide's test: four chips hold two of eight experts
    each; each computes its experts' part and the shared expert whole. The
    four outputs less three copies of the shared expert's are the uncut
    layer's (``plain_kimi_linear.experts`` holding all eight, a selection
    bias of zero), with no selection bias in the program's tree."""
    E, K, D, M, T = 8, 2, 32, 16, 48
    rng = np.random.default_rng(1)

    def normal(*shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape).astype(np.float32)
                           * scale)

    x, wr = normal(T, D), normal(D, E, scale=D ** -0.5)
    wg, wu, wd = (normal(E, D, M, scale=D ** -0.5),
                  normal(E, D, M, scale=D ** -0.5),
                  normal(E, M, D, scale=M ** -0.5))
    shared = {name: {"kernel": normal(*shape, scale=shape[0] ** -0.5)}
              for name, shape in (("gate", (D, M)), ("up", (D, M)),
                                  ("down", (M, D)))}
    total = 0.0
    for first in range(0, E, 2):
        layer = KimiSparseMoe(E, 2, first, K, M, 2.5, dtype=jnp.float32,
                              select_bias=False)
        total = total + layer.apply({"params": {
            "router": wr, "shared": shared,
            **{name: w[first:first + 2] for name, w in
               (("gate", wg), ("up", wu), ("down", wd))}}}, x[None])[0]
    alone = plain_kimi._swiglu(x, shared, jnp.float32)
    uncut = plain_kimi.experts(
        x, {"router": wr, "select_bias": jnp.zeros(E), "gate": wg,
            "up": wu, "down": wd, "shared": shared}, top_k=K,
        first_expert=0, routed_scale=2.5, dtype=jnp.float32)
    assert _rel(total - 3 * alone, uncut) <= 1e-5


# --------------------------------------------------------------------------
# the configuration's arithmetic

def _config():
    return (cell_lib.load_json(CONFIG + ".json"),
            cell_lib.load_module(CONFIG + ".py", "laguna_config"))


def test_parameter_count_by_hand():
    """The docstring of the configuration's ``.py``, and the published
    model's 33.4 B."""
    full = 2048 * 6144 + 2 * 2048 * 1024 + 6144 * 2048 + 2048 * 48
    windowed = 2048 * 8192 + 2 * 2048 * 1024 + 8192 * 2048 + 2048 * 64
    expert, router, norms = 3 * 2048 * 512, 2048 * 256, 2 * 2048
    assert (full, windowed, expert) == (29_458_432, 37_879_808, 3_145_728)
    first = full + norms + 3 * 2048 * 8192
    held = norms + router + 17 * expert
    ends = 2 * 12_544 * 2048 + 2048
    assert (first, windowed + held, full + held, ends) == (
        79_794_176, 91_885_568, 83_464_192, 51_382_272)
    cfg, config = _config()
    assert cfg["n_params"] == first + 3 * (windowed + held) + full + held \
        + ends == 490_297_344
    init, _ = config.build(cfg)
    shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
    assert sum(math.prod(x.shape)
               for x in jax.tree_util.tree_leaves(shapes)) == cfg["n_params"]
    whole = norms + router + 257 * expert
    published = (10 * full + 30 * windowed + 40 * norms + 3 * 2048 * 8192
                 + 39 * (whole - norms) + 2 * 100_352 * 2048 + 2048)
    assert published == 33_442_596_864
    # every width is the published one; the three lists stand whole
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"],
            cfg["sliding_window"], cfg["moe_intermediate_size"],
            cfg["intermediate_size"], cfg["num_experts"],
            cfg["num_experts_per_tok"]) == (2048, 128, 8, 512, 512, 8192,
                                            256, 8)
    assert len(cfg["layer_types"]) == len(cfg["mlp_layer_types"]) == len(
        cfg["num_attention_heads_per_layer"]) == 40


def test_flops_per_token_by_hand():
    """Needed pairs only: the causal triangle in the two global layers, the
    band in the three windowed ones."""
    cfg, config = _config()
    s = 8_192
    triangle, band = s * (s + 1) // 2, 512 * s - 130_816
    assert (triangle, band) == (33_558_528, 4_063_488)
    assert (config.needed_pairs(s), config.needed_pairs(s, 512)) == (
        triangle, band)
    pairs = 2 * 48 * 1536 * triangle + 3 * 64 * 1536 * band
    assert round(2 * 48 * 1536 * triangle / 1e12, 3) == 4.948
    assert round(3 * 64 * 1536 * band / 1e12, 3) == 1.198
    moe = 524_288 + 1_572_864 + 3_145_728
    row = 6 * (2 * 29_458_432 + 3 * 37_879_808 + 50_331_648 + 4 * moe)
    assert row == 1_463_156_736
    head = 6 * 2048 * 12_544
    assert config.flops_per_token(cfg) == (
        s * row + pairs + (s - 1) * head) // s == 2_367_617_664
    assert round(100 * pairs / (s * 2_367_617_664)) == 32
    reader = cell_lib.load_module(
        os.path.join(REPO, "benchmark", "layers", "swa.py"), "swa_reader")
    assert reader.needed_pairs(s, 512) == band
    assert 2 * reader.attend_flops(1, s, 48, 128) == 2 * 48 * 1536 * triangle
    assert 3 * reader.attend_flops(1, s, 64, 128, 512) == 3 * 64 * 1536 * band


# --------------------------------------------------------------------------
# tracing, and the normal path

def test_scopes_nest_in_the_compiled_program():
    """``bps.swa.window`` and ``bps.swa.full`` enclose ``full_attention``'s
    own scope, ``bps.swa.proj`` the projections, forward and backward; the
    share keeps ``bps.moe.route`` / ``bps.moe.experts``."""
    model, params, tokens = _model_and_params(1)
    names = set(re.findall(r'op_name="([^"]*)"', jax.jit(jax.value_and_grad(
        lambda p: laguna_loss(model.apply(p, tokens)))).lower(
            params).compile().as_text()))
    for scope in ("bps.swa.window", "bps.swa.full", "bps.swa.proj",
                  "bps.moe.shared", "bps.moe.route", "bps.moe.experts",
                  "bps.lm.head"):
        for pass_ in ("/jvp(", "/transpose(jvp("):
            assert any((f"/{scope}/" in n or f"({scope})" in n) and pass_ in n
                       for n in names), (scope, pass_)
    for outer in ("bps.swa.window", "bps.swa.full"):
        assert any(f"/{outer}/bps.attn.xla/" in n for n in names), outer
    assert not any("bps.attn.xla" in n and "bps.swa." not in n
                   for n in names)


def test_stats_are_sown_only_when_asked_for():
    model, params, tokens = _model_and_params()
    nll, stats = model.apply(params, tokens, mutable=["moe_stats"])
    assert nll.shape == (2, 31)
    counts = jax.tree_util.tree_leaves(stats["moe_stats"])
    assert len(counts) == 3 and all(int(c.sum()) == 2 * 32 * 2
                                    for c in counts)


def test_the_model_trains_through_make_train_step_on_the_mesh():
    """bps.init() -> make_train_step(loss_fn, adamw) -> step on 8 virtual
    chips: the first loss is the single-device loss of the same batch and
    the loss falls."""
    import byteps_tpu.jax as bps
    from byteps_tpu.jax.training import (make_train_step, replicate,
                                         shard_batch)

    model, params, tokens = _model_and_params(8, 32)

    def loss_fn(p, batch):
        return laguna_loss(model.apply(p, batch["tokens"]))

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
    assert importlib.import_module("benchmark.lib.plain_laguna") is plain

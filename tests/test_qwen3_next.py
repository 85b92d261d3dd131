"""Qwen3NextModel and what it brought (tier-1, CPU, float32, seeded): the
chunked scan's per-head form (one decay a head, key heads under value
heads), the flash kernels 256 wide, the zero-centred norm, the gated shared
expert and the softmax share of ``KimiSparseMoe``.

Yardsticks that share no code with the program: the token-by-token
recurrence (``gated_delta_rule`` in ``benchmark/lib/plain_qwen3_next.py``)
for the chunked scan and for the model, XLA's two einsums for the kernels,
``lax.top_k`` over dense experts for the expert layer. In float32 on the CPU
both sides differ by the order sums are taken in: a relative 1e-5 of the
largest entry wherever nothing discrete can flip (the tolerances below say
where they are wider, and why).
"""

import hashlib
import importlib
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import byteps_tpu.parallel.linear_attention as la
from byteps_tpu.models import (KimiLinearTiny, Qwen3Next80BA3B,
                               Qwen3NextTiny, kimi_linear_loss,
                               qwen3_next_loss)
from byteps_tpu.models.kimi_linear import KimiSparseMoe
from byteps_tpu.models.llama import RMSNorm
from byteps_tpu.models.qwen3_next import (GatedAttention, ZeroCentredNorm,
                                          layer_kinds)
from byteps_tpu.monitor import metrics
from byteps_tpu.ops.flash_attention import _blocks, flash_attention
from byteps_tpu.parallel.linear_attention import (HEAD_SITES, SCAN_SITES,
                                                  kda_attention, kda_form,
                                                  publish_kda_stats)
from byteps_tpu.parallel.moe import publish_moe_stats
from byteps_tpu.parallel.ring_attention import (_single_device_attention,
                                                attention_form,
                                                full_attention)

ra = importlib.import_module("byteps_tpu.parallel.ring_attention")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.lib import cell as cell_lib  # noqa: E402
from benchmark.lib import plain_qwen3_next as plain  # noqa: E402

CONFIG = os.path.join(REPO, "benchmark", "configs", "qwen3-next-80b-a3b")
PLAIN = dict(key_dim=16, head_dim=16, rope_theta=1e7,
             partial_rotary_factor=0.25, top_k=2, first_expert=0, eps=1e-6,
             dtype=jnp.float32, scan_block=16, query_block=16, head_rows=32)


def _rel(got, want):
    return float(jnp.abs(got - want).max()) / max(
        float(jnp.abs(want).max()), 1e-30)


@pytest.fixture(autouse=True)
def _highest():
    """float32 matmuls at float32 on both sides of every comparison."""
    with jax.default_matmul_precision("highest"):
        yield


# --------------------------------------------------------------------------
# the chunked scan, one decay a head

def _gdn_inputs(s, strength, b=2, h_k=2, h=4, d_k=8, d_v=6, seed=0):
    """q, k normalised as the model normalises them, ``h_k`` key heads
    under ``h`` value heads; ``strength`` scales the log-decay (8 is there
    to pass e^-88 inside a chunk)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    return (unit(jax.random.normal(ks[0], (b, s, h_k, d_k))) * d_k ** -0.5,
            unit(jax.random.normal(ks[1], (b, s, h_k, d_k))),
            jax.random.normal(ks[2], (b, s, h, d_v)),
            -strength * jax.nn.softplus(jax.random.normal(ks[3], (b, s, h))),
            jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h))),
            jax.random.normal(ks[5], (b, s, h, d_v)))


def recurrence(q, k, v, g, beta, **kwargs):
    """The plain reference's token-by-token scan, a sequence at a time."""
    return jax.vmap(lambda *row: plain.gated_delta_rule(
        *row, scan_block=q.shape[1], **kwargs))(q, k, v, g, beta)


@pytest.mark.parametrize("s,chunk,strength,h_k", [
    (64, 16, 0.1, 2),      # the chunk divides s; a weak decay
    (50, 16, 1.0, 2),      # it does not: 14 zero tokens close the last chunk
    (64, 32, 8.0, 2),      # a chunk's cumulated log-decay goes under -88
    (33, 8, 16.0, 2),
    (48, 16, 1.0, 4),      # as many key heads as value heads
    (48, 16, 1.0, 1),      # one key head under all four
])
def test_per_head_scan_is_the_token_recurrence(s, chunk, strength, h_k):
    """Values and all five gradients, 2 key heads under 4 value heads. 1e-5:
    nothing discrete; the chunked form sums a chunk's pairs in another order
    than its rank-one updates. No overflow and no clamp at the strong decay:
    every gradient is finite and is the recurrence's."""
    *args, weight = _gdn_inputs(s, strength, h_k=h_k)
    before = metrics.counter(HEAD_SITES)

    def chunked(*a):
        return kda_attention(*a, chunk=chunk, sub=chunk, dtype=jnp.float32)

    assert _rel(chunked(*args), recurrence(*args)) <= 1e-5
    got, want = (jax.jit(jax.grad(lambda *a, f=f: (f(*a) * weight).sum(),
                                  argnums=(0, 1, 2, 3, 4)))(*args)
                 for f in (chunked, recurrence))
    for g, w in zip(got, want):
        assert bool(jnp.isfinite(g).all())
        assert _rel(g, w) <= 1e-5
    assert metrics.counter(HEAD_SITES) > before
    if strength >= 8.0:
        G = jnp.cumsum(la.chunked(args[3], chunk), axis=2)
        assert float(G.min()) < -88.8
        assert not bool(jnp.isfinite(jnp.exp(-G)).all())


@pytest.mark.parametrize("form", ("xla", "kernel"))
def test_one_decay_a_channel_takes_key_heads_under_value_heads(form,
                                                              monkeypatch):
    """The per-channel forms repeat a key head for its value heads; the same
    number on every channel is the per-head rule."""
    if form == "kernel":
        monkeypatch.setattr(la, "kda_form", lambda *shapes: "kernel")
    q, k, v, g, beta, _ = _gdn_inputs(32, 1.0)
    wide = jnp.broadcast_to(g[..., None], (*g.shape, q.shape[-1]))
    got = kda_attention(q, k, v, wide, beta, chunk=16, sub=4,
                        dtype=jnp.float32)
    assert _rel(got, recurrence(q, k, v, g, beta)) <= 1e-5


@pytest.mark.parametrize("wrong", ("state", "grouping", "clamp"))
def test_the_tolerance_fails_the_tool_s_controls(wrong):
    """A state rounded to bf16 after every token, value head i reading key
    head i % 2 and not i // 2, a chunk's cumulated log-decay clamped at -20
    (what a form that exponentiates its negation does to stay finite): each
    is over ten times the 1e-5 away (``tools/scan_check.py`` holds the
    compiled scan to the same three on the chip)."""
    from tools.scan_check import clamped_in_chunks

    q, k, v, g, beta, _ = _gdn_inputs(64, 8.0)
    got = kda_attention(q, k, v, g, beta, chunk=16, sub=16,
                        dtype=jnp.float32)
    kwargs = {"state": dict(state_dtype=jnp.bfloat16),
              "grouping": dict(key_head_of=[0, 1, 0, 1]), "clamp": {}}[wrong]
    if wrong == "clamp":
        floored = clamped_in_chunks(g, 16, -20.0)
        assert float(jnp.cumsum(la.chunked(floored, 16), axis=2).min()) \
            == pytest.approx(-20.0, rel=1e-5)
        # a floor no chunk reaches leaves the decay as it is
        assert _rel(clamped_in_chunks(g, 16, -1e4), g) <= 1e-6
        g = floored
    assert _rel(got, recurrence(q, k, v, g, beta, **kwargs)) > 1e-4


def test_shapes_are_checked():
    q, k, v, g, beta, _ = _gdn_inputs(16, 1.0)
    with pytest.raises(ValueError, match="divisor"):
        kda_attention(q, k, v[:, :, :3], g[:, :, :3], beta[:, :, :3],
                      chunk=16, sub=4)
    with pytest.raises(ValueError, match="beta"):
        kda_attention(q, k, v, g[:, :, :2], beta, chunk=16, sub=4)
    with pytest.raises(ValueError, match="beta"):
        kda_attention(q, k, v, g[..., None], beta, chunk=16, sub=4)


@pytest.mark.parametrize("args,form", [
    # one decay a head: the per-head form wherever it runs, since PR 59 as
    # kernels under the per-channel kernels' rule of the shapes (the table
    # of its refusals is tests/test_gdn_kernel.py's)
    (("tpu", 32, 128, 128, jnp.bfloat16, 64, True, 32), "head"),
    (("tpu", 32, 128, 128, jnp.bfloat16, 32, True, 16), "head_kernel"),
    (("tpu", 32, 128, 128, jnp.float32, 32, True, 16), "head"),
    (("cpu", 32, 128, 128, jnp.float32, 64, True, 32), "head"),
    (("tpu", 4, 16, 16, jnp.bfloat16, 8, True, 4), "head"),
    # one a channel: what it was (the Kimi-Linear cell's shapes first)
    (("tpu", 32, 128, 128, jnp.bfloat16, 32, False, 32), "kernel"),
    (("tpu", 32, 128, 128, jnp.bfloat16, 32, False, 8), "kernel"),
    (("cpu", 32, 128, 128, jnp.bfloat16, 32, False, 32), "xla"),
    (("tpu", 32, 128, 128, jnp.float32, 32, False, 32), "xla"),
    (("tpu", 32, 64, 64, jnp.bfloat16, 32, False, 32), "xla"),
])
def test_the_form_is_a_pure_function_of_backend_and_shapes(args, form):
    assert kda_form(*args) == form


def test_the_kimi_linear_step_lowers_to_what_it_did():
    """PR 50 gave the scan a second rank of decay, key heads under value
    heads and a third form. With one decay a channel, the gradient of
    KimiLinearTiny's loss lowers to the text it lowered to at ``42d0a9d``
    in the XLA form; the kernel form (interpreted here: the same trace the
    chip's compile starts from) lowers to PR 54's text, which gave its scan
    over chunks to the recurrence kernels and changed nothing ahead of
    them."""
    model, tokens = KimiLinearTiny(), np.zeros((2, 32), np.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)

    def digest():
        with jax.default_matmul_precision(None):   # not this file's fixture
            text = jax.jit(jax.grad(lambda p: kimi_linear_loss(
                model.apply(p, tokens)))).lower(params).as_text()
        return hashlib.sha256(text.encode()).hexdigest()

    assert digest() == (
        "b2d53e86b524a8167de69dcaf98d2906ed0e1d3dbe1521b4a16101a7a66ea98c")
    with pytest.MonkeyPatch.context() as m:
        m.setattr(la, "kda_form", lambda *shapes: "kernel")
        assert digest() == (
            "52e66df7ef2c64873f0e3d0e974da1e31d00209a34286ad03a65398dfe2822f4")


# --------------------------------------------------------------------------
# the flash kernels 256 wide

# (s_q, s_k, d, window) of every call the ten cells before PR 50 make and
# what ``_blocks`` returns for it: the four rows at widths 64 and 128 pinned
# at ``42d0a9d`` (the parent of PR 50, which added 256), the two at 192 at
# PR 51's sweep (512 x 512 until then; ``_blocks``'s docstring)
BLOCKS_BEFORE = (
    ((1024, 1024, 64, None), (1024, 1024)),     # GPT-2, both cells
    ((4096, 4096, 128, None), (1024, 1024)),    # OLMoE, Ouro
    ((8192, 8192, 128, None), (1024, 1024)),    # Laguna's global layers
    ((8192, 8192, 128, 512), (512, 512)),       # Laguna's windowed layers
    ((8192, 8192, 192, None), (1024, 1024)),    # Kimi-Linear's latent layer
    ((8192, 8192, 192, None), (1024, 1024)),    # JoyAI's six, 12 forwards
)


@pytest.mark.parametrize("call, blocks", BLOCKS_BEFORE)
def test_blocks_at_the_three_old_widths_are_what_they_were(call, blocks):
    assert _blocks(*call) == blocks


@pytest.mark.parametrize("s", (4096, 8192, 16384))
def test_blocks_at_192(s):
    """The sweep's choice at keys 192 / values 128 (``_blocks``'s docstring,
    PERF.md section 6, PR 51): the cells' sequence and its two neighbours,
    the lengths PR 39 and PR 51 measured."""
    assert _blocks(s, s, 192) == (1024, 1024)


@pytest.mark.parametrize("s", (8192, 16384))
def test_blocks_at_256(s):
    """The sweep's choice (``_blocks``'s docstring, PERF.md section 6, PR
    50), at the cell's sequence and the one ISSUE 39's rule asks about."""
    assert _blocks(s, s, 256) == (1024, 1024)


@pytest.mark.parametrize("args,form", [
    (("tpu", 8192, 8192, 256, True, jnp.bfloat16), "kernel"),
    (("tpu", 8192, 8192, 256, True, jnp.bfloat16, 256), "kernel"),
    (("tpu", 256, 256, 256, True, jnp.bfloat16), "xla"),      # too short
    (("tpu", 8192, 8192, 256, True, jnp.float32), "xla"),
    (("tpu", 8192, 8192, 256, True, jnp.bfloat16, 128), "xla"),
    (("cpu", 8192, 8192, 256, True, jnp.bfloat16), "xla"),
])
def test_the_rule_admits_256_wide_heads(args, form):
    assert attention_form(*args) == form


def test_the_256_wide_kernels_are_the_xla_form():
    """16 query heads over 2 key heads of 256, 8 a key head as the cell's:
    the interpreted kernels against XLA's two einsums over repeated keys,
    forward and three gradients (float32 both: 1e-5)."""
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 256, 16, 256)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((1, 256, 2, 256)), jnp.float32)
            for _ in range(2))
    weight = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)

    def kernel(q, k, v):
        return flash_attention(q, k, v, True, 256 ** -0.5, 128, 128)

    def xla(q, k, v):
        k, v = (jnp.repeat(x, 8, axis=2) for x in (k, v))
        return _single_device_attention(q, k, v, causal=True,
                                        scale=256 ** -0.5)

    assert _rel(kernel(q, k, v), xla(q, k, v)) <= 1e-5
    got, want = (jax.grad(lambda *a, f=f: (f(*a) * weight).sum(),
                          argnums=(0, 1, 2))(q, k, v) for f in (kernel, xla))
    for g, w in zip(got, want):
        assert g.shape == w.shape and _rel(g, w) <= 1e-5


# --------------------------------------------------------------------------
# norms, rotation, gates

def test_the_zero_centred_norm():
    """``x rsqrt(mean x^2 + eps) (1 + w)``, w from 0: at its initial value
    the unit-scale RMSNorm, and a scale of w is the other's 1 + w."""
    x = jnp.asarray(np.random.default_rng(0).standard_normal((3, 5, 16)),
                    jnp.float32)
    norm = ZeroCentredNorm(1e-6)
    params = norm.init(jax.random.PRNGKey(0), x)
    assert not bool(params["params"]["scale"].any())
    want = x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + 1e-6)
    assert _rel(norm.apply(params, x), want) <= 1e-6
    w = jnp.linspace(-0.5, 0.5, 16)
    assert _rel(norm.apply({"params": {"scale": w}}, x),
                want * (1.0 + w)) <= 1e-6
    assert _rel(RMSNorm(1e-6).apply({"params": {"scale": 1.0 + w}}, x),
                norm.apply({"params": {"scale": w}}, x)) <= 1e-6


def _attention_layer(s=32):
    layer = GatedAttention(4, 2, 16, 1e7, 0.25, jnp.float32)
    x = jnp.asarray(np.random.default_rng(1).standard_normal((1, s, 64)),
                    jnp.float32)
    return layer, layer.init(jax.random.PRNGKey(2), x), x


def test_the_rotation_turns_the_first_quarter_and_passes_the_rest():
    """``partial_rotary_factor`` 0.25: entries 4.. of a 16-wide head are
    untouched by position, entries 0..3 turn in pairs (j, j + 2) — read off
    ``plain.rotate`` and the model's ``_rope`` alike."""
    from byteps_tpu.models.llama import _rope

    x = jnp.asarray(np.random.default_rng(0).standard_normal((1, 8, 2, 16)),
                    jnp.float32)
    turned = _rope(x, jnp.arange(8)[None], 1e7, rotary_dim=4)
    assert np.array_equal(np.asarray(turned[..., 4:]), np.asarray(x[..., 4:]))
    assert np.array_equal(np.asarray(turned[:, 0]), np.asarray(x[:, 0]))
    assert not np.allclose(np.asarray(turned[:, 1:, :, :4]),
                           np.asarray(x[:, 1:, :, :4]))
    want = plain.rotate(x[0], *plain.rotary_of(16, 1e7, 0.25))
    assert _rel(turned[0], want) <= 1e-6
    angle = 3 * 1e7 ** (-2 * 1 / 4)           # row 3, pair (1, 3)
    np.testing.assert_allclose(
        np.asarray(turned[0, 3, 0, 1]),
        np.asarray(x[0, 3, 0, 1] * math.cos(angle)
                   - x[0, 3, 0, 3] * math.sin(angle)), rtol=1e-5)


def test_the_output_gate_is_a_number_a_channel():
    """The second half of every head's query projection gates that head's
    output channel by channel: with its columns zeroed every gate is 1/2,
    and one channel's column moves that channel alone (before ``W_o``)."""
    layer, params, x = _attention_layer()
    kernel = params["params"]["q"]["kernel"]                # [64, 4 * 32]
    gate_columns = (jnp.arange(kernel.shape[1]) % 32) >= 16

    def before_wo(kernel):
        p = {"params": {**params["params"], "q": {"kernel": kernel},
                        "o": {"kernel": jnp.eye(64)}}}
        return layer.apply(p, x)

    open_half = before_wo(jnp.where(gate_columns, 0.0, kernel))
    # head 1, channel 5's gate to sigmoid(large) = 1: that channel doubles
    column = 1 * 32 + 16 + 5
    lifted = jnp.where(gate_columns, 0.0, kernel).at[:, column].set(
        1e3 * jnp.sign(x[0, 0]))
    moved = before_wo(lifted)
    changed = np.asarray(jnp.abs(moved - open_half).max(axis=(0, 1)) > 0)
    assert changed.tolist() == [i == 1 * 16 + 5 for i in range(64)]
    np.testing.assert_allclose(np.asarray(moved[0, 0, 21]),
                               2 * np.asarray(open_half[0, 0, 21]),
                               rtol=1e-5)


# --------------------------------------------------------------------------
# the expert layer: softmax, a gated shared expert, a share

T, D, M, E, K = 48, 32, 24, 16, 3


def _layer_inputs(seed=0, t=T):
    rng = np.random.default_rng(seed)
    scale = 1.0 / math.sqrt(D)
    shapes = {"x": (t, D), "router": (D, E), "gate": (E, D, M),
              "up": (E, D, M), "down": (E, M, D), "sg": (D, 1),
              "s_gate": (D, M), "s_up": (D, M), "s_down": (M, D)}
    return {name: jnp.asarray(rng.standard_normal(shape).astype(np.float32)
                              * (0.5 if name == "router" else scale
                                 if name != "x" else 1.0))
            for name, shape in shapes.items()}


@pytest.mark.parametrize("t", (T, 512))
def test_the_shares_parts_add_up_with_the_gated_shared_expert_counted_once(t):
    """The model-configs guide's test: 16 experts over 4 shares of 4
    (``first_expert`` 0, H, 2H, 3H), top-3 of a softmax renormalised; each
    share computes its experts' part and the gated shared expert whole. The
    four outputs less three copies of the gated shared expert's are the
    uncut layer's (``plain.experts`` holding all sixteen); so are the four
    load-balance losses, each the whole router's."""
    a = _layer_inputs(t=t)
    shared = {"gate": {"kernel": a["s_gate"]}, "up": {"kernel": a["s_up"]},
              "down": {"kernel": a["s_down"]}}
    total, losses = 0.0, []
    for first in range(0, E, 4):
        layer = KimiSparseMoe(E, 4, first, K, M, 1.0, dtype=jnp.float32,
                              select_bias=False, scoring="softmax",
                              shared_gate=True, aux=True)
        y, load_balance = layer.apply({"params": {
            "router": a["router"], "shared": shared,
            "shared_gate": {"kernel": a["sg"]},
            **{name: a[name][first:first + 4]
               for name in ("gate", "up", "down")}}}, a["x"][None])
        total = total + y[0]
        losses.append(float(load_balance))
    alone = (jax.nn.sigmoid(a["x"] @ a["sg"])
             * plain._swiglu(a["x"], shared, jnp.float32))
    uncut, want_loss = plain.experts(
        a["x"], {"router": a["router"], "gate": a["gate"], "up": a["up"],
                 "down": a["down"], "shared": shared,
                 "shared_gate": {"kernel": a["sg"]}},
        top_k=K, first_expert=0, dtype=jnp.float32)
    assert _rel(total - 3 * alone, uncut) <= 1e-5
    np.testing.assert_allclose(losses, float(want_loss), rtol=1e-5)


def test_the_softmax_gate_is_top_k_over_dense_experts():
    a = _layer_inputs()
    weight, load_balance = plain.gate_weights(a["x"], a["router"], K)
    np.testing.assert_allclose(np.asarray(weight.sum(-1)), 1.0, rtol=1e-6)
    assert np.array_equal(np.asarray((weight > 0).sum(-1)), np.full(T, K))
    probs = jax.nn.softmax(a["x"] @ a["router"], axis=-1)
    kth = jnp.sort(probs, axis=-1)[:, -K]
    assert bool(((weight > 0) == (probs >= kth[:, None])).all())
    counts = (weight > 0).sum(0)
    np.testing.assert_allclose(
        float(load_balance),
        float(E / (T * K) * (counts * probs.mean(0)).sum()), rtol=1e-6)


def test_the_old_layer_is_what_it_was():
    """``scoring``, ``shared_gate`` and ``aux`` at their defaults: the
    sigmoid gate, a shared expert every token passes whole, one output."""
    a = _layer_inputs()
    layer = KimiSparseMoe(E, 4, 0, K, M, 2.446, dtype=jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), a["x"][None])
    assert "shared_gate" not in params["params"]
    assert isinstance(layer.apply(params, a["x"][None]), jax.Array)


# --------------------------------------------------------------------------
# the model

def _model_and_params(rows=2, s=64):
    model = Qwen3NextTiny(dtype=jnp.float32)
    tokens = np.random.default_rng(0).integers(
        0, 512, (rows, s)).astype(np.int32)
    return model, model.init(jax.random.PRNGKey(0), tokens), tokens


def _plain_loss(p, tokens, **kwargs):
    nll, load_balance = plain.causal_lm_nll(p, tokens, **{**PLAIN, **kwargs})
    return nll.mean() + 0.001 * load_balance


@pytest.mark.parametrize("rows", (1, 2))
def test_model_loss_and_gradients_are_the_plain_reference_s(rows):
    """Through three Gated DeltaNet layers (2 key heads under 4 value heads,
    chunks of 8 against token by token) and a gated attention layer, each
    with an expert layer (2 of 8 held, top-2, a gated shared expert) and the
    load-balance term. Loss 1e-6; gradients 5e-5 of a leaf's largest entry:
    four layers' sums in another order. Every leaf has a gradient."""
    model, params, tokens = _model_and_params(rows)
    got, want = (jax.jit(jax.value_and_grad(f))(params) for f in (
        lambda p: qwen3_next_loss(model.apply(p, tokens)),
        lambda p: _plain_loss(p, tokens)))
    assert abs(float(got[0]) - float(want[0])) <= 1e-6 * float(want[0])
    flat = jax.tree_util.tree_leaves_with_path(got[1])
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want[1])):
        assert _rel(g, w) <= 5e-5, jax.tree_util.keystr(path)
        assert bool(w.any()), jax.tree_util.keystr(path)


@pytest.mark.parametrize("wrong", ("state", "aux", "rotary"))
def test_the_comparison_fails_what_it_should(wrong):
    """The reference with its state rounded to bf16 after every token,
    without the load-balance term, or with half a head rotated: each over
    ten times the 1e-6 the program is held to."""
    model, params, tokens = _model_and_params()
    loss = float(qwen3_next_loss(model.apply(params, tokens)))
    if wrong == "aux":
        other = float(plain.causal_lm_nll(params, tokens, **PLAIN)[0].mean())
    else:
        other = float(_plain_loss(params, tokens, **{
            "state": dict(state_dtype=jnp.bfloat16),
            "rotary": dict(partial_rotary_factor=0.5)}[wrong]))
    assert abs(loss - other) > 1e-5 * loss


def test_layer_kinds_follow_the_interval():
    kinds = layer_kinds(4, 48)
    assert kinds[:4] == ("linear_attention",) * 3 + ("full_attention",)
    assert kinds.count("linear_attention") == 36
    assert kinds == Qwen3Next80BA3B().layer_kinds
    with pytest.raises(ValueError, match="layer_kinds"):
        Qwen3NextTiny(layer_kinds=("linear_attention", "ssm")).init(
            jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))


def _config():
    return (cell_lib.load_json(CONFIG + ".json"),
            cell_lib.load_module(CONFIG + ".py", "qwen3_next_config"))


def test_parameter_count_by_hand():
    """The docstring of the configuration's ``.py``, and the published
    model: 79.7 B — the name's 80B."""
    gdn = (2048 * 12288 + 2048 * 64 + 4 * 8192 + 32 + 32 + 128 + 4096 * 2048)
    attention = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 256
    expert, router, gate, norms = 3 * 2048 * 512, 2048 * 512, 2048, 2 * 2048
    assert (gdn, attention, expert) == (33_718_464, 27_263_488, 3_145_728)
    held = router + expert + gate + 32 * expert
    assert held == 104_859_648
    ends = 2 * 18_992 * 2048 + 2048
    assert (gdn + norms + held, attention + norms + held, ends) == (
        138_582_208, 132_127_232, 77_793_280)
    cfg, module = _config()
    assert cfg["n_params"] == 3 * (gdn + norms + held) + (
        attention + norms + held) + ends == 625_667_136
    init, _ = module.build(cfg)
    shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
    assert sum(math.prod(x.shape) for x in
               jax.tree_util.tree_leaves(shapes)) == cfg["n_params"]
    whole = norms + router + gate + 513 * expert
    published = (36 * (gdn + whole) + 12 * (attention + whole)
                 + 2 * 151_936 * 2048 + 2048)
    assert published == 79_674_391_296
    active = published - 48 * 502 * expert
    assert round(active / 1e9, 1) == 3.9     # the name's A3B, embeddings in


def test_flops_per_token_by_hand():
    cfg, module = _config()
    gdn = 25_165_824 + 131_072 + 8_388_608
    attention = 16_777_216 + 2_097_152 + 8_388_608
    moe = 1_048_576 + 3_145_728 + 2_048 + 1_966_080
    assert (gdn, attention, moe) == (33_685_504, 27_262_976, 6_162_432)
    recurrence = 3 * 7 * 32 * 128 * 128
    assert recurrence == 11_010_048
    row = 6 * (3 * gdn + attention + 4 * moe) + 3 * recurrence
    assert row == 950_845_440
    for s, pairs, head, want in (
            (8_192, 201_351_168, 233_345_208, 1_385_541_816),
            (16_384, 402_677_760, 233_359_452, 1_586_882_652)):
        assert pairs == 6 * 2 * 256 * 16 * (s + 1) // 2
        assert head == (s - 1) * 6 * 2048 * 18_992 // s
        got = module.flops_per_token({**cfg, "seq_len": s})
        assert abs(got - (row + pairs + head)) <= 1 and got == want
    assert module.flops_per_token(cfg) == module.flops_per_token(
        {**cfg, "seq_len": cfg["seq_len"]})


def test_the_configuration_file_against_the_catalog_row():
    """Every key of the catalog row's ``config`` as published, but the three
    in ``reduced``."""
    import json

    cfg, _ = _config()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    assert cfg["source"] == row["source_url"]
    differing = {k for k, v in row["config"].items() if cfg[k] != v}
    assert differing == {"num_hidden_layers", "vocab_size"}
    assert (cfg["num_local_experts"], cfg["num_experts"]) == (32, 512)
    assert [r.split()[0] for r in cfg["reduced"]] == [
        "num_hidden_layers", "num_local_experts", "vocab_size"]


def test_stats_are_sown_only_when_asked_for_and_published():
    model, params, tokens = _model_and_params()
    nll, load_balance = model.apply(params, tokens)
    assert nll.shape == (2, 63) and load_balance.shape == ()
    _, stats = model.apply(params, tokens,
                           mutable=["moe_stats", "kda_stats"])
    counts = jax.tree_util.tree_leaves(stats["moe_stats"])
    assert len(counts) == 4 and all(int(c.sum()) == 2 * 64 * 2
                                    for c in counts)
    decays = jax.tree_util.tree_leaves(stats["kda_stats"])
    assert len(decays) == 3 and all(float(d) < 0 for d in decays)
    out = publish_kda_stats(stats["kda_stats"])
    assert out["bps_kda_min_chunk_log_decay"] == min(map(float, decays))
    held = publish_moe_stats(stats["moe_stats"], held=(0, 2))
    assert 0.0 < held["bps_moe_held_load"] < 4.0


def test_scopes_and_the_site_counters():
    """Each span of the tracing is in the lowered program, forward and
    backward, and a trace of the model counts its three scan sites, all of
    the per-head form."""
    model, params, tokens = _model_and_params(1)
    before = metrics.counter(SCAN_SITES), metrics.counter(HEAD_SITES)
    text = jax.jit(jax.grad(lambda p: qwen3_next_loss(
        model.apply(p, tokens)))).lower(params).as_text(debug_info=True)
    assert metrics.counter(SCAN_SITES) - before[0] >= 3
    assert (metrics.counter(HEAD_SITES) - before[1]
            == metrics.counter(SCAN_SITES) - before[0])
    for scope in ("bps.gdn.prep", "bps.gdn.scan", "bps.gdn.out",
                  "bps.gattn.attend", "bps.gattn.proj", "bps.moe.shared",
                  "bps.moe.route"):
        assert f"/{scope}/" in text, scope
        assert any(scope in line and "transpose(" in line
                   for line in text.splitlines()), scope
    assert "/bps.kda.scan/" not in text and "/bps.kda.prep/" not in text
    # the shared expert's gate is inside the shared expert's scope
    assert any("bps.moe.shared" in line and "shared_gate" in line
               for line in text.splitlines())


def test_the_model_trains_through_make_train_step_on_the_mesh():
    """bps.init() -> make_train_step(loss_fn, adamw) -> step on 8 virtual
    chips: the first loss is the single-device loss of the same batch and
    the loss falls."""
    import byteps_tpu.jax as bps
    from byteps_tpu.jax.training import (make_train_step, replicate,
                                         shard_batch)

    model, params, tokens = _model_and_params(8, 32)

    def loss_fn(p, batch):
        return qwen3_next_loss(model.apply(p, batch["tokens"]))

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
    assert importlib.import_module("benchmark.lib.plain_qwen3_next") is plain


def test_full_attention_hands_256_wide_heads_to_the_kernel(monkeypatch):
    """On a ``tpu`` backend in bf16 ``full_attention`` takes the kernel form
    at (256, 256) and counts the site (the rule is told the backend; the
    kernel itself still sees the CPU and interprets)."""
    rule = ra.attention_form
    monkeypatch.setattr(ra, "attention_form",
                        lambda backend, *rest: rule("tpu", *rest))
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 512, 8, 256)), jnp.bfloat16)
    k, v = (jnp.asarray(rng.standard_normal((1, 512, 1, 256)), jnp.bfloat16)
            for _ in range(2))
    before = metrics.counter(ra.KERNEL_SITES)
    text = jax.jit(lambda q, k, v: full_attention(
        q, k, v, causal=True)).lower(q, k, v).as_text(debug_info=True)
    assert metrics.counter(ra.KERNEL_SITES) == before + 1
    assert "bps.attn.kernel" in text

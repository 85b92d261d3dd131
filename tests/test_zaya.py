"""ZayaModel and what it brought (tier-1, CPU, float32, seeded): compressed
convolutional attention (two causal convolutions, the q-k mean, the value
shift, normalised keys under a temperature, attention inside the latent),
the MLP router with depth averaging and its top-1, residual scaling, the
tied head, and ``dropless_moe_ffn`` taking its logits from its caller.

Yardsticks that share no code with the program: ``benchmark/lib/
plain_zaya.py`` for the model and its layers, and for the mixer a third
writing in numpy with a Python loop over the query heads (``_by_heads``). In
float32 on the CPU both sides differ by the order sums are taken in.
"""

import hashlib
import importlib
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from scipy.special import erf

from byteps_tpu.models import (JoyAIFlashTiny, LagunaTiny, Qwen3NextTiny,
                               Zaya1_8B, ZayaTiny, joyai_loss, laguna_loss,
                               qwen3_next_loss, zaya_loss)
from byteps_tpu.models.kimi_linear import causal_conv
from byteps_tpu.models.zaya import (CCA_SITES, CompressedConvAttention,
                                    ScaledResidual, ZayaRouter,
                                    ZayaSparseMoe, grouped_causal_conv,
                                    shift_tokens)
from byteps_tpu.monitor import metrics
from byteps_tpu.parallel.moe import (dropless_moe_ffn, held_row_bound,
                                     publish_moe_stats)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.lib import cell as cell_lib  # noqa: E402
from benchmark.lib import plain_zaya as plain  # noqa: E402

CONFIG = os.path.join(REPO, "benchmark", "configs", "zaya1-8b")
PLAIN = dict(head_dim=16, rope_theta=5e6, partial_rotary_factor=0.5,
             first_expert=0, eps=1e-5, dtype=jnp.float32, query_block=16,
             head_rows=32)
HEADS, KV, D, WIDTH, S = 4, 2, 16, 64, 24     # the tiny mixer; d_model 64


def _rel(got, want):
    return float(jnp.abs(got - want).max()) / max(
        float(jnp.abs(want).max()), 1e-30)


@pytest.fixture(autouse=True)
def _highest():
    """float32 matmuls at float32 on both sides of every comparison."""
    with jax.default_matmul_precision("highest"):
        yield


def _stirred(params, seed=1):
    """``params`` with every vector that starts at 0 or 1 (norms, residual
    scales, biases, temperature, depth averaging, the balancing bias) moved
    off it, so that each shows in the result."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    out = []
    for (path, leaf), key in zip(leaves, keys):
        name = jax.tree_util.keystr(path)
        if "select_bias" in name:
            leaf = leaf + 0.02 * jax.random.normal(key, leaf.shape)
        elif leaf.ndim == 1 or "conv1_bias" in name:
            leaf = leaf + 0.1 * jax.random.normal(key, leaf.shape)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(tree, out)


# --------------------------------------------------------------------------
# the mixer

def _mixer(s=S, seed=0, **kwargs):
    layer = CompressedConvAttention(HEADS, KV, D, 5e6, 0.5,
                                    dtype=jnp.float32, **kwargs)
    h = jnp.asarray(np.random.default_rng(seed).standard_normal(
        (2, s, WIDTH)), jnp.float32)
    return layer, _stirred(layer.init(jax.random.PRNGKey(seed), h)), h


def _with(params, **leaves):
    """``params`` of a mixer with the named leaves replaced."""
    p = dict(params["params"])
    for name, value in leaves.items():
        p[name] = ({"kernel": jnp.asarray(value, jnp.float32)}
                   if isinstance(p[name], dict)
                   else jnp.asarray(value, jnp.float32))
    return {"params": p}


def _without_convolutions(params):
    """z = 0: what is left of q and k is the q-k mean."""
    p = params["params"]
    return _with(params, **{name: np.zeros(p[name].shape) for name in (
        "conv0", "conv0_bias", "conv1", "conv1_bias")})


def _by_heads(params, h, tau=None):
    """The mixer without its convolutions, one sequence, in numpy float64
    with a loop over the query heads: head i's query is (q~[i] + k~[i //
    G]) / 2, key head j's key (the mean of ITS G query heads' q~ + k~[j]) /
    2; both normalised to sqrt(d), the key times tau_j; the first d / 2
    entries rotated half against half at 5e6^(-2j / (d / 2)); causal
    softmax at d^-1/2; key head 0's value the current token's, key head
    1's the previous token's; ``o W_o``."""
    p = jax.tree_util.tree_map(lambda x: np.asarray(x, np.float64),
                               params["params"])
    h = np.asarray(h, np.float64)
    s, group = h.shape[0], HEADS // KV
    q_in = (h @ p["q"]["kernel"]).reshape(s, HEADS, D)
    k_in = (h @ p["k"]["kernel"]).reshape(s, KV, D)
    before = np.concatenate([np.zeros((1, WIDTH)), h[:-1]])
    values = [h @ p["v1"]["kernel"], before @ p["v2"]["kernel"]]
    tau = np.exp(p["temperature"]) if tau is None else tau

    def turned(x):
        rotary = D // 2
        angle = (np.arange(s)[:, None]
                 * 5e6 ** (-np.arange(rotary // 2) / (rotary // 2)))
        a, b = x[:, :rotary // 2], x[:, rotary // 2:rotary]
        return np.concatenate([a * np.cos(angle) - b * np.sin(angle),
                               a * np.sin(angle) + b * np.cos(angle),
                               x[:, rotary:]], axis=1)

    def unit(x):
        return math.sqrt(D) * x / np.linalg.norm(x, axis=1, keepdims=True)

    keys = []
    for j in range(KV):
        own = [q_in[:, i] for i in range(HEADS) if i // group == j]
        keys.append(turned(tau[j] * unit(
            0.5 * (sum(own) / len(own) + k_in[:, j]))))
    out = []
    for i in range(HEADS):
        j = i // group
        q = turned(unit(0.5 * (q_in[:, i] + k_in[:, j])))
        logits = q @ keys[j].T / math.sqrt(D)
        logits[np.triu_indices(s, 1)] = -np.inf
        probs = np.exp(logits - logits.max(axis=1, keepdims=True))
        out.append(probs / probs.sum(axis=1, keepdims=True) @ values[j])
    return np.concatenate(out, axis=1) @ p["o"]["kernel"]


def test_the_q_k_mean_takes_the_group_as_an_axis():
    """With both convolutions zeroed q and k are the mean alone: query head
    i with key head i // 2, the keys' mean over their two query heads, then
    the normalisation, the temperature, the rotation of entries 0..7 of 16,
    the softmax in the latent and the shifted value, against the loop over
    heads."""
    layer, params, h = _mixer()
    params = _without_convolutions(params)
    got = layer.apply(params, h)
    for row in range(2):
        assert _rel(got[row], _by_heads(params, h[row])) <= 2e-5


def test_scaling_the_projections_leaves_the_logits_unchanged():
    """q^ and k^ have the length sqrt(d) whatever q and k have: W_q and W_k
    times 7 (and with them the convolutions' input and the mean; their
    biases zero) give the same output."""
    layer, params, h = _mixer()
    p = params["params"]
    params = _with(params, conv0_bias=np.zeros(p["conv0_bias"].shape),
                   conv1_bias=np.zeros(p["conv1_bias"].shape))
    scaled = _with(params, q=7.0 * p["q"]["kernel"], k=7.0 * p["k"]["kernel"])
    assert _rel(layer.apply(scaled, h), layer.apply(params, h)) <= 2e-5


def test_the_temperature_scales_the_keys_a_key_head():
    """theta_j + ln 3 is tau_j times 3: the loop over heads with that tau,
    and another result than before."""
    layer, params, h = _mixer()
    params = _without_convolutions(params)
    theta = np.asarray(params["params"]["temperature"])
    hot = _with(params, temperature=theta + np.array([math.log(3.0), 0.0]))
    got = layer.apply(hot, h)[0]
    want = _by_heads(params, h[0], tau=np.exp(theta) * np.array([3.0, 1.0]))
    assert _rel(got, want) <= 2e-5
    assert _rel(got, layer.apply(params, h)[0]) > 1e-2


def test_the_rotation_turns_the_first_half_of_a_head():
    """``_by_heads`` turns entries 0..7 of 16 and agrees; a layer that
    turns all 16 does not."""
    layer, params, h = _mixer()
    params = _without_convolutions(params)
    whole = CompressedConvAttention(HEADS, KV, D, 5e6, 1.0,
                                    dtype=jnp.float32)
    want = _by_heads(params, h[0])
    assert _rel(layer.apply(params, h)[0], want) <= 2e-5
    assert _rel(whole.apply(params, h)[0], want) > 1e-2


def test_key_head_1_carries_the_previous_token_s_value():
    """With W_o the identity a row of the output is the heads' outputs side
    by side. Token 0 sees itself alone: query heads 0, 1 (key head 0) give
    h_0 W_v1, query heads 2, 3 (key head 1) the zero before the sequence.
    Token 1's heads 2, 3 see a zero and h_0 W_v2: a multiple of h_0 W_v2."""
    layer, params, h = _mixer()
    p = params["params"]
    params = _with(params, o=np.eye(WIDTH))
    out = np.asarray(layer.apply(params, h)).reshape(2, S, HEADS, D)
    now = np.asarray(h[:, 0] @ p["v1"]["kernel"])
    before = np.asarray(h[:, 0] @ p["v2"]["kernel"])
    for head in (0, 1):
        assert np.abs(out[:, 0, head] - now).max() <= 1e-5
    for head in (2, 3):
        assert np.abs(out[:, 0, head]).max() == 0.0
        cosine = (out[:, 1, head] * before).sum(-1) / (
            np.linalg.norm(out[:, 1, head], axis=-1)
            * np.linalg.norm(before, axis=-1))
        assert np.abs(cosine - 1.0).max() <= 1e-5


def test_shift_and_convolutions_read_no_later_token():
    x = jnp.asarray(np.random.default_rng(0).standard_normal((1, 6, 3, 4)),
                    jnp.float32)
    assert (np.asarray(shift_tokens(x))[0, 1:] == np.asarray(x)[0, :-1]).all()
    assert not np.asarray(shift_tokens(x))[0, 0].any()
    w = jnp.asarray(np.random.default_rng(1).standard_normal((2, 3, 4, 4)),
                    jnp.float32)
    got = np.asarray(grouped_causal_conv(x, w, jnp.float32))[0]
    x64, w64 = np.asarray(x, np.float64)[0], np.asarray(w, np.float64)
    for t in range(6):
        for head in range(3):
            want = x64[t, head] @ w64[1, head]      # the last tap: token t
            if t:
                want = want + x64[t - 1, head] @ w64[0, head]
            assert np.abs(got[t, head] - want).max() <= 1e-5
    flat = x.reshape(1, 6, 12)
    taps = jnp.asarray([[2.0] * 12, [3.0] * 12])
    assert np.abs(np.asarray(causal_conv(flat, taps))[0, 2]
                  - (2.0 * flat[0, 1] + 3.0 * flat[0, 2])).max() <= 1e-5


@pytest.mark.parametrize("t", (0, 5, S - 2))
def test_the_mixer_is_causal(t):
    """Other rows after token t: every output up to t is the same to the
    bit — through both convolutions, the value shift and the mask."""
    layer, params, h = _mixer()
    other = h.at[:, t + 1:].set(jnp.asarray(
        np.random.default_rng(9).standard_normal((2, S - t - 1, WIDTH)),
        jnp.float32))
    got, again = layer.apply(params, h), layer.apply(params, other)
    assert (np.asarray(got[:, :t + 1]) == np.asarray(again[:, :t + 1])).all()
    assert _rel(again[:, t + 1:], got[:, t + 1:]) > 1e-2


@pytest.mark.parametrize("wrong", ("value_shift", "swap_taps", "qk_mean",
                                   "temperature", "norm_dtype"))
def test_the_mixer_is_the_plain_reference_s_and_none_of_its_controls(wrong):
    """The controls of ``tools/attention_check.py`` on the CPU: the plain
    mixer as written agrees to 2e-5, and with the value shift dropped,
    conv1's taps swapped (a convolution that reads token t + 1's tap at t),
    the q-k mean left out, the temperature ignored or a bf16 normalisation
    it is another function."""
    layer, params, h = _mixer()
    rotary = plain.rotary_of(D, 5e6, 0.5)

    def reference(**control):
        return jax.vmap(lambda row: plain.cca(
            row, params["params"], head_dim=D, rotary=rotary,
            dtype=jnp.float32, query_block=8, **control))(h)

    got = layer.apply(params, h)
    assert _rel(got, reference()) <= 2e-5
    control = {"norm_dtype": jnp.bfloat16} if wrong == "norm_dtype" else {
        wrong: wrong == "swap_taps"}
    assert _rel(got, reference(**control)) > 2e-3


# --------------------------------------------------------------------------
# the router and the expert sublayer

E, M, R, T = 4, 32, 16, 48


def _gelu(x):
    return 0.5 * x * (1.0 + erf(x / math.sqrt(2.0)))


def _router_by_hand(p, g, r_prev=None):
    p = jax.tree_util.tree_map(lambda x: np.asarray(x, np.float64), p)
    r = np.asarray(g, np.float64) @ p["down"]["kernel"]
    if r_prev is not None:
        r = r + p["depth_decay"] * np.asarray(r_prev, np.float64)
    hidden = r
    for name in ("mlp_1", "mlp_2"):
        hidden = _gelu(hidden @ p[name]["kernel"] + p[name]["bias"])
    return hidden @ p["mlp_3"]["kernel"], r


@pytest.mark.parametrize("first", (True, False))
def test_the_router_is_a_down_projection_a_mix_and_an_mlp(first):
    """Layer 0 adds nothing and has no gamma; a later block adds gamma *
    r_prev; what it hands on is r after the mix, before the MLP."""
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.standard_normal((1, T, WIDTH)), jnp.float32)
    r_prev = None if first else jnp.asarray(
        rng.standard_normal((1, T, R)), jnp.float32)
    router = ZayaRouter(R, E)
    params = _stirred(router.init(jax.random.PRNGKey(0), g, r_prev))
    assert ("depth_decay" in params["params"]) == (not first)
    assert "bias" not in params["params"]["mlp_3"]
    logits, r = router.apply(params, g, r_prev)
    want_logits, want_r = _router_by_hand(params["params"], g, r_prev)
    assert _rel(logits, want_logits) <= 2e-5 and _rel(r, want_r) <= 2e-5
    if not first:
        alone, _ = _router_by_hand(params["params"], g, 0.0 * r_prev)
        assert _rel(want_logits, alone) > 1e-3


def _moe_layer(held=E, first_expert=0, seed=0):
    layer = ZayaSparseMoe(E, held, first_expert, M, R, jnp.float32)
    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.standard_normal((1, T, WIDTH)), jnp.float32)
    r_prev = jnp.asarray(rng.standard_normal((1, T, R)), jnp.float32)
    return layer, layer.init(jax.random.PRNGKey(seed), g, r_prev), g, r_prev


def _dense_top1(p, g, r_prev, bal):
    """Every expert on every token in float64; token t keeps p[e*] times
    expert e*'s output, e* = argmax(p + bal)."""
    logits, _ = _router_by_hand(p["router"], g[0], r_prev[0])
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    chosen = np.argmax(probs + bal, axis=-1)
    w = jax.tree_util.tree_map(lambda x: np.asarray(x, np.float64), p)
    x = np.asarray(g[0], np.float64)
    out = np.zeros_like(x)
    for t, e in enumerate(chosen):
        gate = x[t] @ w["gate"][e]
        out[t] = probs[t, e] * ((gate / (1 + np.exp(-gate))
                                 * (x[t] @ w["up"][e])) @ w["down"][e])
    return out, chosen, probs


def test_top_1_is_argmax_of_p_plus_bal_and_the_weight_is_p():
    """A balancing bias that favours expert 3 changes the choice of most
    tokens and never a weight: the output is p[e*] (not 1, not p + bal)
    times the chosen expert's."""
    layer, params, g, r_prev = _moe_layer()
    bal = np.array([0.0, 0.0, 0.0, 0.3])
    biased = {"params": {**params["params"],
                         "select_bias": jnp.asarray(bal, jnp.float32)}}
    plain_choice = _dense_top1(params["params"], g, r_prev, 0.0)[1]
    want, chosen, probs = _dense_top1(params["params"], g, r_prev, bal)
    assert (chosen != plain_choice).sum() >= T // 8
    assert (chosen == 3).sum() > (plain_choice == 3).sum()
    got, _ = layer.apply(biased, g, r_prev)
    assert _rel(got[0], want) <= 2e-5
    assert float(probs.max()) < 0.9          # a weight far from 1
    # no gradient reaches the bias
    grad = jax.grad(lambda p: layer.apply(p, g, r_prev)[0].sum())(biased)
    assert not np.asarray(grad["params"]["select_bias"]).any()
    assert np.asarray(grad["params"]["router"]["mlp_3"]["kernel"]).any()


def test_the_two_shares_parts_add_up_to_the_plain_uncut_layer():
    """Experts 0..1 on one chip, 2..3 on the other, router and state on
    both: the two parts sum to what the plain reference gives with all four
    experts, and both hand on the same r."""
    whole, params, g, r_prev = _moe_layer()
    want, want_r = plain.experts(g[0], params["params"], r_prev[0],
                                 first_expert=0, dtype=jnp.float32)
    parts = []
    for first in (0, 2):
        share = ZayaSparseMoe(E, 2, first, M, R, jnp.float32)
        held = {"params": {**params["params"], **{
            name: params["params"][name][first:first + 2]
            for name in ("gate", "up", "down")}}}
        y, r = share.apply(held, g, r_prev)
        assert _rel(r[0], want_r) <= 2e-5
        parts.append(y[0])
        assert float(jnp.abs(y).max()) > 0.0
    assert _rel(parts[0] + parts[1], want) <= 2e-5
    assert _rel(whole.apply(params, g, r_prev)[0][0], want) <= 2e-5


@pytest.mark.parametrize("held,first", ((E, 0), (2, 2)))
def test_the_layer_given_logits_is_the_layer_given_the_router(held, first):
    """``logits = x router_w`` handed over is the function computing them
    itself, whole and as a share, outputs and gradients; both or neither is
    refused."""
    rng = np.random.default_rng(0)
    x, router_w = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
                   for shape in ((T, WIDTH), (WIDTH, E)))
    w_gate, w_up = (jnp.asarray(rng.standard_normal((held, WIDTH, M)) / 8,
                                jnp.float32) for _ in range(2))
    w_down = jnp.asarray(rng.standard_normal((held, M, WIDTH)) / 6,
                         jnp.float32)
    kwargs = dict(top_k=2, dtype=jnp.float32, first_expert=first,
                  norm_topk=True)

    def own(x, router_w):
        return dropless_moe_ffn(x, router_w, w_gate, w_up, w_down, **kwargs)

    def handed(x, router_w):
        logits = jnp.dot(x, router_w, precision=jax.lax.Precision.HIGHEST)
        return dropless_moe_ffn(x, None, w_gate, w_up, w_down, **kwargs,
                                logits=logits)

    for got, want in zip(handed(x, router_w), own(x, router_w)):
        assert (np.asarray(got) == np.asarray(want)).all()
    grads = [jax.grad(lambda *a: f(*a)[0].sum() + f(*a)[1], argnums=(0, 1))(
        x, router_w) for f in (handed, own)]
    for got, want in zip(*grads):
        assert _rel(got, want) <= 1e-6
    for router, logits in ((router_w, jnp.zeros((T, E))), (None, None)):
        with pytest.raises(ValueError, match="one of the two"):
            dropless_moe_ffn(x, router, w_gate, w_up, w_down, top_k=1,
                             logits=logits)


def test_the_residual_merge_scales_both_sides():
    rng = np.random.default_rng(0)
    x, y = (jnp.asarray(rng.standard_normal((2, 3, 8)), jnp.float32)
            for _ in range(2))
    merge = ScaledResidual()
    fresh = merge.init(jax.random.PRNGKey(0), x, y)
    assert (np.asarray(merge.apply(fresh, x, y)) == np.asarray(x + y)).all()
    p = _stirred(fresh)["params"]
    want = ((p["stream_scale"] * x + p["stream_bias"])
            + (p["branch_scale"] * y + p["branch_bias"]))
    assert _rel(merge.apply({"params": p}, x, y), want) <= 1e-6
    assert _rel(want, x + y) > 1e-2


# --------------------------------------------------------------------------
# the model

def _model_and_params(rows=2, s=64):
    model = ZayaTiny(dtype=jnp.float32)
    tokens = np.random.default_rng(0).integers(
        0, 512, (rows, s)).astype(np.int32)
    return model, _stirred(model.init(jax.random.PRNGKey(0), tokens)), tokens


def _plain_loss(p, tokens, **kwargs):
    return plain.causal_lm_nll(p, tokens, **{**PLAIN, **kwargs}).mean()


@pytest.mark.parametrize("rows", (1, 2))
def test_model_loss_and_gradients_are_the_plain_reference_s(rows):
    """Through five blocks (4 query heads over 2 key heads of 16, a router
    16 wide over 4 experts with 2 held, top-1), the router's state handed
    from block to block, non-unit residual scales and the tied head. Loss
    1e-6; gradients 5e-5 of a leaf's largest entry: five layers' sums in
    another order. Every leaf but the balancing bias has a gradient."""
    model, params, tokens = _model_and_params(rows)
    got, want = (jax.jit(jax.value_and_grad(f))(params) for f in (
        lambda p: zaya_loss(model.apply(p, tokens)),
        lambda p: _plain_loss(p, tokens)))
    assert abs(float(got[0]) - float(want[0])) <= 1e-6 * float(want[0])
    flat = jax.tree_util.tree_leaves_with_path(got[1])
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want[1])):
        name = jax.tree_util.keystr(path)
        assert _rel(g, w) <= 5e-5, name
        assert bool(w.any()) == ("select_bias" not in name), name


@pytest.mark.parametrize("wrong", ("rotary", "first_expert", "eps"))
def test_the_comparison_fails_what_it_should(wrong):
    """The reference with a whole head rotated, with the other share's
    experts' columns of the weights, or with another epsilon in its norms:
    each over ten times the 1e-6 the program is held to."""
    model, params, tokens = _model_and_params()
    loss = float(zaya_loss(model.apply(params, tokens)))
    other = float(_plain_loss(params, tokens, **{
        "rotary": dict(partial_rotary_factor=1.0),
        "first_expert": dict(first_expert=2),
        "eps": dict(eps=1e-2)}[wrong]))
    assert abs(loss - other) > 1e-5 * loss


@pytest.mark.parametrize("t", (0, 17, 62))
def test_the_model_is_causal(t):
    """Other tokens after t: the loss terms of the tokens up to t (row i
    predicts token i + 1, so rows 0 .. t - 1) are the same to the bit."""
    model, params, tokens = _model_and_params()
    other = tokens.copy()
    other[:, t + 1:] = (other[:, t + 1:] + 7) % 512
    got, again = model.apply(params, tokens), model.apply(params, other)
    assert (np.asarray(got[:, :t]) == np.asarray(again[:, :t])).all()
    assert (np.asarray(got[:, t:]) != np.asarray(again[:, t:])).all()


def test_the_head_is_the_embedding_and_the_final_norm_starts_small():
    model = ZayaTiny(dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    p = params["params"]
    assert "lm_head" not in p
    assert np.allclose(np.asarray(p["final_norm"]["scale"]),
                       math.log(512) / 64)
    for i in range(5):
        norms = (p[f"layer_{i}"]["mixer"]["norm"]["scale"],
                 p[f"layer_{i}"]["ffn"]["norm"]["scale"])
        assert all((np.asarray(n) == 1.0).all() for n in norms)
    assert ("depth_decay" in p["layer_0"]["ffn"]["moe"]["router"]) is False
    assert "depth_decay" in p["layer_1"]["ffn"]["moe"]["router"]
    # moving the embedding moves the loss through both of its uses
    tokens = np.arange(16, dtype=np.int32)[None]
    grad = jax.grad(lambda q: zaya_loss(model.apply(q, tokens)))(params)
    rows = np.asarray(grad["params"]["embed"]["embedding"])
    assert rows[:16].any() and rows[16:].any()


def _config():
    return (cell_lib.load_json(CONFIG + ".json"),
            cell_lib.load_module(CONFIG + ".py", "zaya_config"))


def test_parameter_count_by_hand():
    """The docstring of the configuration's ``.py``, and the published
    model: 8.30 B outside the embedding — the family's 8.3 B."""
    mixer = (2048 * 1024 + 2048 * 256 + 2 * 2048 * 128 + 1024 * 2048
             + 2 * 1280 + 1280 + 2 * 10 * 128 * 128 + 1280 + 2)
    router = 2048 * 256 + 2 * (256 * 256 + 256) + 256 * 16 + 16 + 256
    expert, norms, merges = 3 * 2048 * 2048, 2 * 2048, 2 * 4 * 2048
    assert (mixer, router, 8 * expert) == (5_575_682, 660_240, 100_663_296)
    layer = mixer + router + 8 * expert + norms + merges
    assert layer == 106_919_698
    cfg, module = _config()
    assert cfg["n_params"] == (5 * layer - 256 + 32_784 * 2048 + 2048
                               ) == 601_741_914
    init, _ = module.build(cfg)
    shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
    assert sum(math.prod(x.shape) for x in
               jax.tree_util.tree_leaves(shapes)) == cfg["n_params"]
    published = (40 * (layer + 8 * expert) - 256 + 262_272 * 2048 + 2048)
    assert published == 8_840_454_608
    assert round((published - 262_272 * 2048) / 1e9, 1) == 8.3
    whole = Zaya1_8B()
    assert (whole.num_layers, whole.vocab_size, whole.num_local_experts) == (
        40, 262_272, 16)


def test_flops_per_token_by_hand():
    cfg, module = _config()
    mixer = 2_097_152 + 524_288 + 524_288 + 2_097_152 + 327_680
    router = 524_288 + 65_536 + 65_536 + 4_096
    held = 3 * 2048 * 2048 * 8 // 16
    assert (mixer, router, held) == (5_570_560, 659_456, 6_291_456)
    row = 5 * 6 * (mixer + router + held)
    assert row == 375_644_160
    for s, pairs, head, want in (
            (8_192, 50_337_792, 402_800_616, 1_030_133_736),
            (16_384, 100_669_440, 402_825_204, 1_281_816_564)):
        assert pairs == 6 * 2 * 128 * 8 * (s + 1) // 2
        assert head == (s - 1) * 6 * 2048 * 32_784 // s
        got = module.flops_per_token({**cfg, "seq_len": s})
        assert abs(got - (row + 5 * pairs + head)) <= 1 and got == want
    assert held_row_bound(16_384, 1, 8, 16) == 16_384


def test_the_configuration_file_against_the_catalog_row():
    """Every key of the catalog row's ``config`` as published, but the two
    of its keys in ``reduced`` (the third counts the experts held)."""
    cfg, _ = _config()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "ZAYA1-8B")
    assert cfg["source"] == row["source_url"]
    differing = {k for k, v in row["config"].items() if cfg[k] != v}
    assert differing == {"num_hidden_layers", "vocab_size"}
    assert (cfg["num_local_experts"], cfg["num_experts"]) == (8, 16)
    assert cfg["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert [r.split()[0] for r in cfg["reduced"]] == [
        "num_hidden_layers", "num_local_experts", "vocab_size"]
    for item in ("cca", "router", "block"):
        assert "could not be read here" in cfg["assumed"][item]
    assert "mod" in cfg["assumed"] and "balancing_bias" in cfg["assumed"]


def test_stats_are_sown_only_when_asked_for_and_published():
    model, params, tokens = _model_and_params()
    assert model.apply(params, tokens).shape == (2, 63)
    _, stats = model.apply(params, tokens, mutable=["moe_stats"])
    counts = jax.tree_util.tree_leaves(stats["moe_stats"])
    assert len(counts) == 5 and all(int(c.sum()) == 2 * 64 for c in counts)
    held = publish_moe_stats(stats["moe_stats"], held=(0, 2))
    assert 0.0 < held["bps_moe_held_load"] < 2.0
    assert held["bps_moe_compact_share"] == 1.0    # half held: one pass


def test_scopes_and_the_site_counter():
    """Each span of the tracing is in the lowered program, forward and
    backward, the router's ahead of the expert layer's own, and a trace of
    the model counts its five mixers."""
    model, params, tokens = _model_and_params(1)
    before = metrics.counter(CCA_SITES)
    text = jax.jit(jax.grad(lambda p: zaya_loss(
        model.apply(p, tokens)))).lower(params).as_text(debug_info=True)
    assert metrics.counter(CCA_SITES) - before >= 5
    for scope in ("bps.cca.proj", "bps.cca.mix", "bps.cca.attend",
                  "bps.moe.router", "bps.moe.route"):
        assert f"/{scope}/" in text, scope
        assert any(f"/{scope}/" in line and "transpose(" in line
                   for line in text.splitlines()), scope
    # full_attention's own scope lies inside the mixer's (the XLA form here)
    assert "/bps.cca.attend/bps.attn.xla/" in text


def test_the_model_trains_through_make_train_step_on_the_mesh():
    """bps.init() -> make_train_step(loss_fn, adamw) -> step on 8 virtual
    chips: the first loss is the single-device loss of the same batch and
    the loss falls."""
    import byteps_tpu.jax as bps
    from byteps_tpu.jax.training import (make_train_step, replicate,
                                         shard_batch)

    model = ZayaTiny(dtype=jnp.float32)
    tokens = np.random.default_rng(0).integers(
        0, 512, (8, 32)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)

    def loss_fn(p, batch):
        return zaya_loss(model.apply(p, batch["tokens"]))

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
    assert importlib.import_module("benchmark.lib.plain_zaya") is plain


# --------------------------------------------------------------------------
# the share cells that had no pin

# sha256 of the lowered gradient of each tiny model's loss at ``d0e19c3``,
# the parent of PR 55, which gave ``dropless_moe_ffn`` its ``logits``
# argument and ``RMSNorm`` its ``initial``: with neither given, the text the
# three share cells without a pin lowered to there (OLMoE's, Keye's and
# Kimi-Linear's pins are in their own files)
PINNED_AT_D0E19C3 = {
    "joyai": (JoyAIFlashTiny, joyai_loss,
              "70a13afbfd2b5134a94dcdc17acf194c32bc5af4898cf8edcc2a637df3593"
              "dbc"),
    "laguna": (LagunaTiny, laguna_loss,
               "0cc79471c7b59f38e875857619568d8748d376883d48b3b0c06df4f0da011"
               "8f0"),
    "qwen3_next": (Qwen3NextTiny, qwen3_next_loss,
                   "70cbc46791aa97620e0b4cbbb94a2415964d0bcf9a5e4eec689d4ae91"
                   "a8c6e4d"),
}


@pytest.mark.parametrize("name", sorted(PINNED_AT_D0E19C3))
def test_the_share_cells_steps_lower_to_what_they_did(name):
    tiny, loss, want = PINNED_AT_D0E19C3[name]
    model, tokens = tiny(), np.zeros((2, 32), np.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    with jax.default_matmul_precision(None):       # not this file's fixture
        text = jax.jit(jax.grad(lambda p: loss(
            model.apply(p, tokens)))).lower(params).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == want

"""NemotronHModel and what it brought (tier-1, CPU, float32, seeded): the
chunked scan with no delta rule (``ssd_scan``: Mamba-2's selective
state-space recurrence, groups of ``B`` and ``C`` under the heads), the
ungated body of ``dropless_moe_ffn``, the convolution's bias, the gated
group norm, a stack of single-mixer layers and the grouped flash kernels at
16 query heads a key head.

Yardsticks that share no code with the program: the token-by-token
recurrence (``selective_scan`` in ``benchmark/lib/plain_nemotron_h.py``)
for the chunked scan and for the model, a dense loop over the experts for
the expert layer, XLA's two einsums for the kernels. In float32 on the CPU
both sides differ by the order sums are taken in: a relative 1e-5 of the
largest entry wherever nothing discrete can flip (the tolerances below say
where they are wider, and why).
"""

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

from byteps_tpu.models import (Nemotron3Nano30BA3B, NemotronHTiny,
                               nemotron_h_loss)
from byteps_tpu.models.kimi_linear import KimiSparseMoe, causal_conv
from byteps_tpu.models.nemotron_h import (NEMOTRON_SITES, Mamba2Mixer,
                                          NemotronAttention)
from byteps_tpu.monitor import metrics
from byteps_tpu.ops.flash_attention import flash_attention
from byteps_tpu.parallel.linear_attention import (SCAN_SITES, SSM_SCAN_SITES,
                                                  publish_kda_stats,
                                                  ssd_scan)
from byteps_tpu.parallel.moe import (UNGATED_SITES, dropless_moe_ffn,
                                     held_row_bound, publish_moe_stats)
from byteps_tpu.parallel.ring_attention import (KERNEL_SITES, XLA_SITES,
                                                _single_device_attention)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.lib import cell as cell_lib  # noqa: E402
from benchmark.lib import plain_nemotron_h as plain  # noqa: E402

CONFIG = os.path.join(REPO, "benchmark", "configs", "nemotron-3-nano-30b-a3b")
PLAIN = dict(state_size=16, head_dim=16, top_k=2, first_expert=0,
             routed_scale=2.5, eps=1e-5, dtype=jnp.float32, scan_block=16,
             query_block=16, head_rows=32)


def _rel(got, want):
    return float(jnp.abs(got - want).max()) / max(
        float(jnp.abs(want).max()), 1e-30)


@pytest.fixture(autouse=True)
def _highest():
    """float32 matmuls at float32 on both sides of every comparison."""
    with jax.default_matmul_precision("highest"):
        yield


# --------------------------------------------------------------------------
# the chunked scan with no delta rule

def _ssd_inputs(s, strength, b=2, groups=2, h=4, n=8, p=6, seed=0):
    """C, B at ``groups`` groups under ``h`` heads, x, the log-decay (times
    ``strength``: 8 is there to pass e^-88 inside a chunk), the step, and a
    cotangent."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    dt = jax.nn.softplus(jax.random.normal(ks[3], (b, s, h)))
    return (jax.random.normal(ks[0], (b, s, groups, n)),
            jax.random.normal(ks[1], (b, s, groups, n)),
            jax.random.normal(ks[2], (b, s, h, p)), -strength * dt, dt,
            jax.random.normal(ks[5], (b, s, h, p)))


def recurrence(c, b, x, g, dt, **kwargs):
    """The plain reference's token-by-token scan, a sequence at a time."""
    return jax.vmap(lambda *row: plain.selective_scan(
        *row, scan_block=x.shape[1], **kwargs))(c, b, x, g, dt)


@pytest.mark.parametrize("s,chunk,strength,groups", [
    (64, 16, 0.1, 2),      # the chunk divides s; a weak decay
    (50, 16, 1.0, 2),      # it does not: 14 zero tokens close the last chunk
    (64, 32, 8.0, 2),      # a chunk's cumulated log-decay goes under -88
    (33, 8, 16.0, 2),
    (128, 4, 1.0, 4),      # as many groups as heads; several groups of chunks
    (48, 16, 1.0, 1),      # one group under all four heads
])
def test_the_chunked_scan_is_the_token_recurrence(s, chunk, strength, groups):
    """Values and all five gradients, fewer groups than heads. 1e-5:
    nothing discrete; the chunked form sums a chunk's pairs in another order
    than its rank-one writes. No overflow and no clamp at the strong decay:
    every gradient is finite and is the recurrence's."""
    *operands, w = _ssd_inputs(s, strength, groups=groups)
    if strength >= 8.0:
        assert float(jnp.cumsum(operands[3][:, :chunk], axis=1).min()) < -88

    def both(fn):
        return jax.value_and_grad(
            lambda *a: (fn(*a) * w).sum(), argnums=range(5))(*operands)

    got = both(lambda *a: ssd_scan(*a, chunk=chunk, dtype=jnp.float32))
    want = both(recurrence)
    assert abs(float(got[0]) - float(want[0])) <= 1e-5 * abs(float(want[0]))
    for name, g, wanted in zip("cbxgd", got[1], want[1]):
        assert bool(jnp.isfinite(g).all()), name
        assert _rel(g, wanted) <= 1e-5, name


@pytest.mark.parametrize("wrong", ("state", "grouping", "clamp"))
def test_the_tolerance_fails_the_tool_s_controls(wrong):
    """A bf16 state, head i reading group i % 2 and a cumulated log-decay
    clamped at -20 each read over a hundred times the 1e-5 the scan is held
    to."""
    from tools.scan_check import clamped_in_chunks

    c, b, x, g, dt, _ = _ssd_inputs(64, 8.0)
    want = recurrence(c, b, x, g, dt)
    if wrong == "clamp":
        got = recurrence(c, b, x, clamped_in_chunks(g, 32, -20.0), dt)
    else:
        got = recurrence(c, b, x, g, dt, **{
            "state": dict(state_dtype=jnp.bfloat16),
            "grouping": dict(group_of=[0, 1, 0, 1])}[wrong])
    assert _rel(got, want) > 1e-3


def test_the_scan_is_causal():
    """A change at token t moves nothing before t."""
    c, b, x, g, dt, _ = _ssd_inputs(48, 1.0)
    base = ssd_scan(c, b, x, g, dt, chunk=16, dtype=jnp.float32)
    t = 21
    for i, operand in enumerate((c, b, x, g, dt)):
        moved = list((c, b, x, g, dt))
        moved[i] = operand.at[:, t].multiply(1.5)
        out = ssd_scan(*moved, chunk=16, dtype=jnp.float32)
        assert bool((out[:, :t] == base[:, :t]).all()), i
        assert bool((out[:, t:] != base[:, t:]).any()), i


def test_scan_shapes_are_checked():
    c, b, x, g, dt, _ = _ssd_inputs(16, 1.0)
    with pytest.raises(ValueError, match="ssd_scan"):
        ssd_scan(c[:, :, :1].repeat(3, 2), b[:, :, :1].repeat(3, 2), x, g,
                 dt)
    with pytest.raises(ValueError, match="ssd_scan"):
        ssd_scan(c, b, x, g[..., None], dt)


def test_the_scan_counts_its_sites_apart_from_the_delta_rule_s():
    operands = _ssd_inputs(16, 1.0)[:5]
    before = metrics.counter(SSM_SCAN_SITES), metrics.counter(SCAN_SITES)
    jax.jit(lambda *a: ssd_scan(*a, chunk=8)).lower(*operands)
    assert metrics.counter(SSM_SCAN_SITES) - before[0] == 1
    assert metrics.counter(SCAN_SITES) == before[1]


# --------------------------------------------------------------------------
# the convolution's bias and the gated group norm

def test_the_convolution_adds_its_bias_and_is_causal():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 12, 5)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((4, 5)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal(5), jnp.float32)
    out = causal_conv(x, w, bias)
    assert bool((out == causal_conv(x, w) + bias).all())
    # token t reads t-3..t: by hand at t = 0 (zeros before) and t = 7
    np.testing.assert_allclose(out[:, 0], x[:, 0] * w[3] + bias, rtol=1e-6)
    np.testing.assert_allclose(
        out[:, 7], sum(x[:, 4 + i] * w[i] for i in range(4)) + bias,
        rtol=1e-6)
    moved = causal_conv(x.at[:, 6].add(1.0), w, bias)
    assert bool((moved[:, :6] == out[:, :6]).all())
    assert bool((moved[:, 10:] == out[:, 10:]).all())
    # and the reference's, a sequence at a time
    np.testing.assert_allclose(
        jax.nn.silu(out[0]), plain.conv_silu(x[0], w, bias), rtol=1e-6)


def _mixer(seed=0, s=24):
    layer = Mamba2Mixer(4, 8, 2, 16, chunk=8, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, s, 64))
    params = layer.init(jax.random.PRNGKey(1), x)
    return layer, params, x


def test_the_gate_comes_before_the_group_norm():
    """``GN(y SiLU(z))``, an RMSNorm over each group's 16 channels: scaling
    one group's gated values leaves the normed output what it was, which a
    norm before the gate would not; the other order reads far off."""
    rng = np.random.default_rng(0)
    y, z = (jnp.asarray(rng.standard_normal((6, 32)), jnp.float32)
            for _ in range(2))
    weight = jnp.asarray(rng.standard_normal(32), jnp.float32)
    got = plain.gated_group_norm(y, z, weight, 2, 1e-5)
    gated = (y * jax.nn.silu(z)).reshape(6, 2, 16)
    want = (gated / jnp.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5)
            ).reshape(6, 32) * weight
    np.testing.assert_allclose(got, want, rtol=1e-5)
    # a group's scale is normalised away, group by group
    scaled = plain.gated_group_norm(
        y * jnp.repeat(jnp.asarray([3.0, 0.5]), 16), z, weight, 2, 1e-5)
    np.testing.assert_allclose(scaled, got, rtol=1e-3)
    normed_first = (y.reshape(6, 2, 16) / jnp.sqrt(
        (y.reshape(6, 2, 16) ** 2).mean(-1, keepdims=True) + 1e-5)
        ).reshape(6, 32) * weight * jax.nn.silu(z)
    assert _rel(normed_first, got) > 0.1
    # the program's mixer is the reference's, which holds that order
    layer, params, x = _mixer()
    p = params["params"]
    ours = layer.apply(params, x)
    theirs = jax.vmap(lambda row: plain._mamba(
        row, p, state_size=16, dtype=jnp.float32, eps=1e-5, scan_block=8,
        state_dtype=jnp.float32))(x)
    assert _rel(ours, theirs) <= 1e-5


def test_the_mixer_is_causal():
    layer, params, x = _mixer()
    base = layer.apply(params, x)
    moved = layer.apply(params, x.at[:, 13].add(1.0))
    assert bool((moved[:, :13] == base[:, :13]).all())
    assert bool((moved[:, 13:] != base[:, 13:]).any())


# --------------------------------------------------------------------------
# the expert layer: ungated experts, a share

T, D, M, E, K = 48, 32, 24, 16, 3


def _layer_inputs(seed=0, t=T):
    rng = np.random.default_rng(seed)
    scale = 1.0 / math.sqrt(D)
    shapes = {"x": (t, D), "router": (D, E), "up": (E, D, M),
              "down": (E, M, D), "bias": (E,), "s_up": (D, 2 * M),
              "s_down": (2 * M, D)}
    return {name: jnp.asarray(rng.standard_normal(shape).astype(np.float32)
                              * {"router": 0.5, "x": 1.0, "bias": 0.3}.get(
                                  name, scale))
            for name, shape in shapes.items()}


def _dense_relu2(a, first, held):
    """Every held expert over every token, weighted: a dense loop."""
    scores = jax.nn.sigmoid(a["x"] @ a["router"])
    _, top_e = jax.lax.top_k(scores + a["bias"], K)
    chosen = (top_e[:, :, None] == jnp.arange(E)).any(1)
    weights = jnp.where(chosen, scores, 0.0)
    weights = weights / weights.sum(-1, keepdims=True) * 2.5
    y = 0.0
    for e in range(first, first + held):
        hidden = jnp.square(jax.nn.relu(a["x"] @ a["up"][e]))
        y = y + weights[:, e:e + 1] * (hidden @ a["down"][e])
    return y


@pytest.mark.parametrize("first,held,t", [
    (0, E, T),        # all experts: the sorted rows, no passes
    (4, 4, T),        # a share, one pass
    (0, 2, 512),      # a share at 512 tokens
])
def test_the_ungated_body_is_a_dense_loop(first, held, t):
    """``w_gate`` None: two grouped matmuls and relu^2, forward and the
    gradients of x and both matrices. 1e-5: the sorted rows' sums in another
    order."""
    a = _layer_inputs(t=t)

    def ours(x, up, down):
        return dropless_moe_ffn(
            x, a["router"], None, up, down, top_k=K, dtype=jnp.float32,
            first_expert=first, norm_topk=True, scoring="sigmoid",
            select_bias=a["bias"], routed_scale=2.5)[0]

    def dense(x, up, down):
        return _dense_relu2({**a, "x": x, "up": jnp.zeros_like(a["up"]).at[
            first:first + held].set(up), "down": jnp.zeros_like(
                a["down"]).at[first:first + held].set(down)}, first, held)

    w = jax.random.normal(jax.random.PRNGKey(3), a["x"].shape)
    operands = (a["x"], a["up"][first:first + held],
                a["down"][first:first + held])
    got, want = (jax.value_and_grad(
        lambda *o: (f(*o) * w).sum(), argnums=(0, 1, 2))(*operands)
        for f in (ours, dense))
    assert abs(float(got[0]) - float(want[0])) <= 1e-5 * abs(float(want[0]))
    for name, g, wanted in zip(("x", "up", "down"), got[1], want[1]):
        assert _rel(g, wanted) <= 1e-5, name


def test_the_ungated_body_takes_several_passes(monkeypatch):
    """With the bound at a quarter of what it is, the share's rows take
    several passes of the loop, forward and backward: the same numbers."""
    moe = importlib.import_module("byteps_tpu.parallel.moe")
    a = _layer_inputs(t=512)
    first, held = 0, 4

    def loss(x, up, down):
        return (dropless_moe_ffn(
            x, a["router"], None, up, down, top_k=K, dtype=jnp.float32,
            first_expert=first, norm_topk=True, scoring="sigmoid",
            select_bias=a["bias"], routed_scale=2.5)[0] ** 2).sum()

    operands = (a["x"], a["up"][:held], a["down"][:held])
    one = jax.value_and_grad(loss, argnums=(0, 1, 2))(*operands)
    monkeypatch.setattr(moe, "held_row_bound", lambda t, k, h, e: 128)
    counts = dropless_moe_ffn(
        a["x"], a["router"], None, *operands[1:], top_k=K,
        dtype=jnp.float32, scoring="sigmoid", select_bias=a["bias"])[3]
    assert int(counts[:held].sum()) > 2 * 128          # three passes or more
    several = jax.value_and_grad(loss, argnums=(0, 1, 2))(*operands)
    assert abs(float(several[0]) - float(one[0])) <= 1e-5 * float(one[0])
    for g, wanted in zip(several[1], one[1]):
        assert _rel(g, wanted) <= 1e-5


def test_the_ungated_sites_are_counted_and_the_gated_ones_are_not():
    a = _layer_inputs()
    before = metrics.counter(UNGATED_SITES)
    dropless_moe_ffn(a["x"], a["router"], None, a["up"], a["down"], top_k=K,
                     dtype=jnp.float32)
    assert metrics.counter(UNGATED_SITES) - before == 1
    dropless_moe_ffn(a["x"], a["router"], a["up"], a["up"], a["down"],
                     top_k=K, dtype=jnp.float32)
    assert metrics.counter(UNGATED_SITES) - before == 1


def test_the_router_is_sigmoid_biased_renormalised_and_scaled():
    """The bias chooses and never weighs; the chosen scores over their sum,
    times 2.5."""
    a = _layer_inputs()
    weight = plain.gate_weights(a["x"], a["router"], a["bias"], K, 2.5)
    np.testing.assert_allclose(np.asarray(weight.sum(-1)), 2.5, rtol=1e-6)
    assert np.array_equal(np.asarray((weight > 0).sum(-1)), np.full(T, K))
    scores = jax.nn.sigmoid(a["x"] @ a["router"])
    kth = jnp.sort(scores + a["bias"], axis=-1)[:, -K]
    chosen = (scores + a["bias"]) >= kth[:, None]
    assert bool(((weight > 0) == chosen).all())
    want = jnp.where(chosen, scores, 0.0)
    np.testing.assert_allclose(
        weight, want / want.sum(-1, keepdims=True) * 2.5, rtol=1e-6)
    # the bias moved the choice somewhere, or the case shows nothing
    plain_kth = jnp.sort(scores, axis=-1)[:, -K]
    assert bool((chosen != (scores >= plain_kth[:, None])).any())
    # and the program's layer weighs as the reference does
    y = dropless_moe_ffn(
        a["x"], a["router"], None, a["up"], a["down"], top_k=K,
        dtype=jnp.float32, norm_topk=True, scoring="sigmoid",
        select_bias=a["bias"], norm_eps=1e-20, routed_scale=2.5)[0]
    assert _rel(y, _dense_relu2(a, 0, E)) <= 1e-5


@pytest.mark.parametrize("t", (T, 512))
def test_sixteen_shares_parts_add_up_with_the_shared_expert_counted_once(t):
    """The model-configs guide's test: 16 experts over 16 shares of 1
    (``first_expert`` 0 .. 15), top-3 of a sigmoid gate with its selection
    bias, renormalised, times 2.5; each share computes its expert's part
    and the ungated shared expert whole. The sixteen outputs less fifteen
    copies of the shared expert's are the uncut layer's (``plain.experts``
    holding all sixteen)."""
    a = _layer_inputs(t=t)
    shared = {"up": {"kernel": a["s_up"]}, "down": {"kernel": a["s_down"]}}
    total = 0.0
    for first in range(E):
        layer = KimiSparseMoe(E, 1, first, K, M, 2.5, 2, jnp.float32,
                              gated=False)
        total = total + layer.apply({"params": {
            "router": a["router"], "select_bias": a["bias"],
            "shared": shared, "up": a["up"][first:first + 1],
            "down": a["down"][first:first + 1]}}, a["x"][None])[0]
    alone = plain._relu2(a["x"], a["s_up"], a["s_down"], jnp.float32)
    uncut = plain.experts(
        a["x"], {"router": a["router"], "select_bias": a["bias"],
                 "up": a["up"], "down": a["down"], "shared": shared},
        top_k=K, first_expert=0, routed_scale=2.5, dtype=jnp.float32)
    assert _rel(total - (E - 1) * alone, uncut) <= 1e-5


OLDER_TREES = {
    # what each of the five older uses of the layer builds, by its fields
    "kimi_linear": (dict(), {"router", "select_bias", "gate", "up", "down",
                             "shared"}),
    "mellum": (dict(shared=0, select_bias=False, scoring="softmax"),
               {"router", "gate", "up", "down"}),
    "qwen3_next": (dict(select_bias=False, scoring="softmax",
                        shared_gate=True, aux=True),
                   {"router", "gate", "up", "down", "shared",
                    "shared_gate"}),
    "zaya": (dict(shared=0, select_bias=False, scoring="softmax", aux=True),
             {"router", "gate", "up", "down"}),
    "laguna": (dict(shared=2), {"router", "select_bias", "gate", "up",
                                "down", "shared"}),
}


@pytest.mark.parametrize("name", sorted(OLDER_TREES))
def test_the_older_parameter_trees_are_what_they_were(name):
    """``gated`` at its default: a gate matrix held and shared, as before."""
    fields, leaves = OLDER_TREES[name]
    a = _layer_inputs()
    layer = KimiSparseMoe(E, 4, 0, K, M, 2.446, dtype=jnp.float32, **fields)
    params = layer.init(jax.random.PRNGKey(0), a["x"][None])["params"]
    assert set(params) == leaves
    if "shared" in leaves:
        assert set(params["shared"]) == {"gate", "up", "down"}
    assert params["gate"].shape == (4, D, M)


def test_the_ungated_tree_has_no_gate_anywhere():
    a = _layer_inputs()
    layer = KimiSparseMoe(E, 4, 0, K, M, 2.5, 2, jnp.float32, gated=False)
    params = layer.init(jax.random.PRNGKey(0), a["x"][None])["params"]
    assert set(params) == {"router", "select_bias", "up", "down", "shared"}
    assert set(params["shared"]) == {"up", "down"}
    assert params["shared"]["up"]["kernel"].shape == (D, 2 * M)


# --------------------------------------------------------------------------
# attention with no rotation, 16 query heads a key head

def test_the_kernels_serve_sixteen_query_heads_a_key_head():
    """The interpreted grouped kernels at 32 query heads over 2 key heads
    against the XLA form, out and the three gradients. 2e-5: the kernels sum
    the softmax block by block."""
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (1, 64, 32, 16))
    k, v = (jax.random.normal(key, (1, 64, 2, 16)) for key in ks[1:3])
    w = jax.random.normal(ks[3], q.shape)

    def both(fn):
        return jax.value_and_grad(
            lambda *a: (fn(*a) * w).sum(), argnums=(0, 1, 2))(q, k, v)

    got = both(lambda q, k, v: flash_attention(
        q, k, v, True, 0.25, 32, 32, True))
    want = both(lambda q, k, v: _single_device_attention(
        q, jnp.repeat(k, 16, 2), jnp.repeat(v, 16, 2), causal=True,
        scale=0.25))
    assert abs(float(got[0]) - float(want[0])) <= 2e-5 * abs(float(want[0]))
    for g, wanted in zip(got[1], want[1]):
        assert _rel(g, wanted) <= 2e-5


def test_attention_is_position_free_but_for_the_causal_mask():
    """No rotation and no norm: the layer is the reference's, and the last
    token's output does not turn on the order of the tokens before it."""
    layer = NemotronAttention(4, 2, 16, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 24, 64))
    params = layer.init(jax.random.PRNGKey(1), x)
    assert set(params["params"]) == {"q", "k", "v", "o"}
    out = layer.apply(params, x)
    want = plain._attention(x[0], params["params"], head_dim=16,
                            dtype=jnp.float32, query_block=8)
    assert _rel(out[0], want) <= 1e-5
    order = np.concatenate([np.random.default_rng(0).permutation(23), [23]])
    np.testing.assert_allclose(layer.apply(params, x[:, order])[0, -1],
                               out[0, -1], rtol=2e-5, atol=1e-6)


# --------------------------------------------------------------------------
# the model

def _model_and_params(rows=2, s=64):
    """The tiny model with every leaf moved off its initial value (a zero
    bias and unit vectors would hide a lost term)."""
    model = NemotronHTiny(dtype=jnp.float32)
    tokens = np.random.default_rng(0).integers(
        0, 512, (rows, s)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(7), len(leaves))
    leaves = [leaf + 0.05 * jax.random.normal(key, leaf.shape)
              if leaf.ndim == 1 else leaf for leaf, key in zip(leaves, keys)]
    return model, jax.tree_util.tree_unflatten(tree, leaves), tokens


def _plain_loss(p, tokens, **kwargs):
    return plain.causal_lm_nll(p, tokens, **{**PLAIN, **kwargs}).mean()


@pytest.mark.parametrize("rows", (1, 2))
def test_model_loss_and_gradients_are_the_plain_reference_s(rows):
    """Through two Mamba-2 layers (2 groups under 4 heads, chunks of 8
    against token by token), two ungated expert layers (2 of 8 held, top-2,
    a shared expert twice as wide) and an attention layer, each one mixer
    under one norm. Loss 1e-6; gradients 5e-5 of a leaf's largest entry:
    five layers' sums in another order. Every leaf but the selection bias,
    which chooses and never weighs, has a gradient."""
    model, params, tokens = _model_and_params(rows)
    got, want = (jax.jit(jax.value_and_grad(f))(params) for f in (
        lambda p: nemotron_h_loss(model.apply(p, tokens)),
        lambda p: _plain_loss(p, tokens)))
    assert abs(float(got[0]) - float(want[0])) <= 1e-6 * float(want[0])
    flat = jax.tree_util.tree_leaves_with_path(got[1])
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want[1])):
        name = jax.tree_util.keystr(path)
        if "select_bias" in name:
            assert not bool(g.any()) and not bool(w.any()), name
            continue
        assert _rel(g, w) <= 5e-5, name
        assert bool(w.any()), name


@pytest.mark.parametrize("wrong", ("scale", "experts"))
def test_the_comparison_fails_what_it_should(wrong):
    """The reference with the routed weights unscaled or with the share one
    expert off: each over ten times the 1e-6 the program's loss is held
    to."""
    model, params, tokens = _model_and_params()
    loss = float(nemotron_h_loss(model.apply(params, tokens)))
    other = float(_plain_loss(params, tokens, **{
        "scale": dict(routed_scale=1.0),
        "experts": dict(first_expert=1)}[wrong]))
    assert abs(loss - other) > 1e-5 * loss


def test_the_gradients_tolerance_fails_a_bf16_state():
    """The reference with its state rounded to bf16 after every token: the
    loss, a mean over every position of a model whose group norm divides a
    layer's scale away, moves by 8e-6 only, but a Mamba-2 layer's gradients
    read over ten times the 5e-5 the program's are held to."""
    model, params, tokens = _model_and_params()
    got, want = (jax.jit(jax.grad(f))(params)["params"]["layer_0"]["ssm"]
                 for f in (
        lambda p: nemotron_h_loss(model.apply(p, tokens)),
        lambda p: _plain_loss(p, tokens, state_dtype=jnp.bfloat16)))
    assert max(_rel(got[name], want[name])
               for name in ("A_log", "dt_bias", "D")) > 5e-4


def test_the_pattern_builds_the_right_mixer_a_layer():
    shapes = jax.eval_shape(
        NemotronHTiny(pattern="M*EEM").init, jax.random.PRNGKey(0),
        np.zeros((1, 8), np.int32))["params"]
    mixers = [next(k for k in shapes[f"layer_{i}"] if k != "norm")
              for i in range(5)]
    assert mixers == ["ssm", "attn", "moe", "moe", "ssm"]
    # one mixer under one norm: no layer holds two
    assert all(len(shapes[f"layer_{i}"]) == 2 for i in range(5))
    whole = Nemotron3Nano30BA3B().pattern
    assert (len(whole), whole.count("M"), whole.count("E"),
            whole.count("*")) == (52, 23, 23, 6)
    with pytest.raises(ValueError, match="pattern"):
        NemotronHTiny(pattern="ME-").init(
            jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))


def _config():
    return (cell_lib.load_json(CONFIG + ".json"),
            cell_lib.load_module(CONFIG + ".py", "nemotron_h_config"))


def test_parameter_count_by_hand():
    """The docstring of the configuration's ``.py``, and the published
    model: 31.6 B, 3.2 B of them active outside the embedding."""
    d = 2688
    mamba = (d * (4096 + 6144 + 64) + 4 * 6144 + 6144 + 3 * 64 + 4096
             + 4096 * d + d)
    attention = 2 * d * 4096 + 2 * d * 256 + d
    expert = 2 * d * 1856
    assert (mamba, attention, expert) == (38_744_896, 23_399_040, 9_977_856)
    outside = d * 128 + 128 + 2 * d * 3712 + d
    held = outside + 8 * expert
    assert held == 100_125_440 and outside + 128 * expert == 1_297_468_160
    ends = 2 * 16_384 * d + d
    cfg, module = _config()
    assert cfg["n_params"] == 4 * mamba + attention + 4 * held + ends \
        == 666_963_456
    init, _ = module.build(cfg)
    shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
    assert sum(math.prod(x.shape) for x in
               jax.tree_util.tree_leaves(shapes)) == cfg["n_params"]
    published = (23 * mamba + 6 * attention + 23 * (outside + 128 * expert)
                 + 2 * 131_072 * d + d)
    assert published == cfg["n_params_published"] == 31_577_940_288
    whole = jax.eval_shape(Nemotron3Nano30BA3B().init, jax.random.PRNGKey(0),
                           np.zeros((1, 8), np.int32))
    assert sum(math.prod(x.shape) for x in
               jax.tree_util.tree_leaves(whole)) == published
    active = published - 23 * 122 * expert - 131_072 * d
    assert round(active / 1e9, 1) == 3.2


def test_init_balances_the_selection_biases():
    """The configuration's ``init`` (``router_balance``): at a small size
    with more experts than the rehearsal's, the busiest expert and the held
    experts' load on tokens the balancing never saw, with and without it."""
    cfg, module = _config()
    small = {**cfg, **cfg["rehearsal_sizing"], "num_hidden_layers": 4,
             "hybrid_override_pattern": "MEME", "n_routed_experts": 32,
             "num_local_experts": 4, "num_experts_per_tok": 4,
             "seq_len": 1024}
    tokens = np.random.default_rng(3).integers(
        0, small["vocab_size"], (1, small["seq_len"]), dtype=np.int32)
    even = small["seq_len"] * 4 / 32

    def loads(passes):
        c = {**small, "router_balance": {**small["router_balance"],
                                         "passes": passes}}
        model = module._model(c)
        params = jax.jit(module.build(c)[0])(jax.random.PRNGKey(3))
        stats = model.apply(params, tokens, mutable=["moe_stats"])[1]
        counts = [np.asarray(layer["moe"]["counts"][0]) for layer in
                  stats["moe_stats"].values()]
        biases = [params["params"][name]["moe"]["select_bias"]
                  for name in stats["moe_stats"]]
        return ([c.max() / even for c in counts],
                [c[:4].sum() / (4 * even) for c in counts], biases)

    busiest, held, biases = loads(0)
    assert max(busiest) > 2.0 and all(not b.any() for b in biases)
    busiest, held, biases = loads(small["router_balance"]["passes"])
    assert max(busiest) < 1.4 and all(b.any() for b in biases)
    assert all(abs(h - 1.0) < 0.1 for h in held)


def test_a_window_sees_no_batch_twice():
    """``batch_pool`` of the cell's traffic file: the warm-up and a window
    of ``run_seconds`` at steps of 0.6 s (the cell's are 0.74) fit it."""
    manifest = cell_lib.load_json(os.path.join(REPO, "BENCHMARK.json"))
    traffic = cell_lib.load_json(os.path.join(
        REPO, "benchmark", "traffic", "collective-ssm.1chip.json"))
    assert traffic["batch_pool"] >= (traffic["warmup_steps"]
                                     + manifest["run_seconds"] / 0.6)


def test_flops_per_token_by_hand():
    cfg, module = _config()
    mamba = 2688 * 10_304 + 4096 * 2688
    attention = 2 * 2688 * 4096 + 2 * 2688 * 256
    moe = 2688 * 128 + 2 * 2688 * 3712 + 6 * 8 * 2 * 2688 * 1856 // 128
    assert (mamba, attention, moe) == (38_707_200, 23_396_352, 24_041_472)
    recurrence = 3 * 5 * 64 * 128 * 64
    assert recurrence == 7_864_320
    row = 6 * (4 * mamba + attention + 4 * moe) + 4 * recurrence
    assert row == 1_677_803_520
    for s, pairs, head, want in (
            (8_192, 201_351_168, 264_208_896, 2_143_363_584),
            (16_384, 402_677_760, 264_225_024, 2_344_706_304)):
        assert pairs == 6 * 2 * 128 * 32 * (s + 1) // 2
        assert head == (s - 1) * 6 * 2688 * 16_384 // s
        got = module.flops_per_token({**cfg, "seq_len": s})
        assert abs(got - (row + pairs + head)) <= 1 and got == want


def test_the_readers_counts_by_hand():
    """``layers/ssm.py``, ``layers/rmoe.py`` and ``layers/nattn.py``: what
    the recurrence, the six grouped matmuls and the causal triangle need at
    the cell's shapes, as their docstrings work them out."""
    cfg, _ = _config()
    ssm, rmoe, nattn = (cell_lib.load_module(os.path.join(
        REPO, "benchmark", "layers", name + ".py"), "layer_" + name)
        for name in ("ssm", "rmoe", "nattn"))
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    tokens = 16_384
    assert ssm.scan_flops(tokens, 64, 128, 64, 4) == 515_396_075_520
    assert ssm.scan_bytes(tokens, 8, 64, 128, 64, 4) == 5_435_817_984
    # bound by bandwidth: 6.637 ms a step at the peak
    at_10_ms = ssm.scan_roofline_pct(10.0, {**cfg, "seq_len": tokens},
                                     tokens, peaks)
    assert round(at_10_ms, 1) == 66.4
    held_rows = 4 * tokens * 6 * 8 // 128
    assert held_rows == 24_576 and rmoe.CALLS == 6
    assert rmoe.gmm_flops(held_rows, 2688, 1856) == 1_471_294_734_336
    assert rmoe.gmm_bytes(held_rows, 8, 2688, 1856, 4) == 3_255_828_480
    # bound by arithmetic: 7.469 ms a step at the peak; 4 expert layers of 9
    assert round(rmoe.gmm_roofline_pct(10.0, cfg, held_rows, peaks),
                 1) == 74.7
    # one attention layer's causal triangle, 32 heads of 128: 33.49 ms
    assert round(nattn.attend_roofline_pct(
        100.0, {**cfg, "seq_len": tokens}, 1, peaks), 2) == 33.49
    for reader in (ssm, rmoe, nattn):
        assert all(name.split(".")[0] == reader.__name__[6:]
                   for name in reader.METRICS)


def test_the_configuration_file_against_the_catalog_row():
    """Every key of the catalog row's ``config`` as published, but those in
    ``reduced``; the pattern here is the published one's first nine."""
    cfg, _ = _config()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    assert cfg["source"] == row["source_url"]
    differing = {k for k, v in row["config"].items() if cfg[k] != v}
    assert differing == {"num_hidden_layers", "hybrid_override_pattern",
                         "vocab_size"}
    assert (cfg["num_local_experts"], cfg["n_routed_experts"]) == (8, 128)
    assert [r.split()[0] for r in cfg["reduced"]] == [
        "num_hidden_layers", "hybrid_override_pattern", "num_local_experts",
        "vocab_size"]
    assert row["config"]["hybrid_override_pattern"].startswith(
        cfg["hybrid_override_pattern"])
    assert len(cfg["hybrid_override_pattern"]) == cfg["num_hidden_layers"]


def test_stats_are_sown_only_when_asked_for_and_published():
    model, params, tokens = _model_and_params()
    assert model.apply(params, tokens).shape == (2, 63)
    _, stats = model.apply(params, tokens,
                           mutable=["moe_stats", "ssm_stats"])
    counts = jax.tree_util.tree_leaves(stats["moe_stats"])
    assert len(counts) == 2 and all(int(c.sum()) == 2 * 64 * 2
                                    for c in counts)
    decays = jax.tree_util.tree_leaves(stats["ssm_stats"])
    assert len(decays) == 2 and all(float(d) < 0 for d in decays)
    out = publish_kda_stats(stats["ssm_stats"],
                            "bps_ssm_min_chunk_log_decay")
    assert out == {"bps_ssm_min_chunk_log_decay": min(map(float, decays))}
    held = publish_moe_stats(stats["moe_stats"], held=(0, 2))
    assert 0.0 < held["bps_moe_held_load"] < 4.0


def test_scopes_and_the_site_counters():
    """Each span of the tracing is in the lowered program, forward and
    backward, and one trace of the loss counts its five layers, two scan
    sites, two ungated expert calls and one attention site (on the CPU the
    XLA form)."""
    model, params, tokens = _model_and_params(1)
    names = (NEMOTRON_SITES, SSM_SCAN_SITES, UNGATED_SITES, XLA_SITES,
             KERNEL_SITES, SCAN_SITES)
    before = [metrics.counter(n) for n in names]
    jax.jit(lambda p: nemotron_h_loss(model.apply(p, tokens))).lower(params)
    assert [metrics.counter(n) - b for n, b in zip(names, before)] == [
        5, 2, 2, 1, 0, 0]
    text = jax.jit(jax.grad(lambda p: nemotron_h_loss(
        model.apply(p, tokens)))).lower(params).as_text(debug_info=True)
    for scope in ("bps.ssm.proj", "bps.ssm.prep", "bps.ssm.scan",
                  "bps.ssm.out", "bps.nattn.attend", "bps.nattn.proj",
                  "bps.moe.route", "bps.moe.shared"):
        assert f"/{scope}/" in text, scope
        assert any(scope in line and "transpose(" in line
                   for line in text.splitlines()), scope
    assert "/bps.kda.scan/" not in text and "/bps.gdn.scan/" not in text


def test_the_share_s_bound_at_the_cell_s_shapes():
    """E 128, k 6, H 8: twice the even part of a layer's 98,304 assignments
    at 16,384 tokens, in 512s."""
    assert held_row_bound(16_384, 6, 8, 128) == 12_288


def test_the_model_trains_through_make_train_step_on_the_mesh():
    """bps.init() -> make_train_step(loss_fn, adamw) -> step on 8 virtual
    chips: the first loss is the single-device loss of the same batch and
    the loss falls."""
    import byteps_tpu.jax as bps
    from byteps_tpu.jax.training import (make_train_step, replicate,
                                         shard_batch)

    model, params, tokens = _model_and_params(8, 32)

    def loss_fn(p, batch):
        return nemotron_h_loss(model.apply(p, batch["tokens"]))

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
    assert "import byteps_tpu" not in source
    assert "from byteps_tpu" not in source

"""JoyAIFlashModel and what it brought (tier-1, CPU, float32, seeded): latent
attention with a query rank and a rotary key part, the interleaved form of
``_rope``, the MTP module through the main model's embedding and head, the
head in row blocks as a function two models and two streams call.

The yardstick shares no code with the program: ``benchmark/lib/
plain_joyai.py`` (the rotation as a complex multiplication, attention in
query blocks, a literal ``argsort`` gate over dense experts, the MTP stream
by explicit shifts over its s - 2 rows and no padding). In float32 on the
CPU both sides differ by the order sums are taken in: 1e-6 of the loss and
1e-5 of a gradient leaf's largest entry (measured: 1.4e-6 at the worst
leaf); nothing discrete can flip at these sizes and seeds. The cases at the
end of each section show what that tolerance fails.
"""

import importlib
import math
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from byteps_tpu.models import (JoyAIFlash48BA3B, JoyAIFlashTiny, joyai_loss,
                               publish_mtp_stats)
from byteps_tpu.models.kimi_linear import (KimiLatentAttention,
                                           KimiSparseMoe, RMSNorm)
from byteps_tpu.models.llama import _rope
from byteps_tpu.monitor import metrics
from byteps_tpu.parallel.ring_attention import full_attention

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.lib import cell as cell_lib  # noqa: E402
from benchmark.lib import plain_joyai as plain  # noqa: E402
from benchmark.lib import plain_kimi_linear as plain_kimi  # noqa: E402

CONFIG = os.path.join(REPO, "benchmark", "configs", "joyai-llm-flash")
PLAIN = dict(heads=4, kv_rank=32, v_dim=16, rope_dim=8, theta=32e6, top_k=2,
             first_expert=0, routed_scale=2.5, eps=1e-6, dtype=jnp.float32,
             query_block=16, head_rows=32)
LAMBDA = 0.3


def _rel(got, want):
    return float(jnp.abs(got - want).max()) / max(
        float(jnp.abs(want).max()), 1e-30)


@pytest.fixture(autouse=True)
def _highest():
    """float32 matmuls at float32 on both sides of every comparison."""
    with jax.default_matmul_precision("highest"):
        yield


def _model_and_params(rows=2, s=64, **over):
    model = JoyAIFlashTiny(dtype=jnp.float32, **over)
    tokens = np.random.default_rng(0).integers(
        0, 512, (rows, s)).astype(np.int32)
    return model, model.init(jax.random.PRNGKey(0), tokens), tokens


def _plain_loss(params, tokens, main_weight=1.0, mtp_weight=LAMBDA, **over):
    main, mtp = plain.causal_lm_nll(params, tokens, **{**PLAIN, **over})
    return main_weight * main.mean() + mtp_weight * mtp.mean()


# --------------------------------------------------------------------------
# the rotation

def _half_split(x, positions, theta):
    """``_rope`` as it stood before it had a second form."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), \
        x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


def test_interleaved_rotation_is_a_complex_multiplication():
    """Pair (2j, 2j + 1) of row p times exp(i p theta^(-2j / d)), against
    numpy's complex128 at the configuration's theta. 2e-6 of the largest
    entry: float32 angles up to 63 radians. Scores depend on the distance
    alone: a common shift of the positions leaves q . k as it was."""
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 64, 3, 16)).astype(np.float32)
    positions = np.broadcast_to(np.arange(64), (2, 64))
    z = x.reshape(2, 64, 3, 8, 2).astype(np.float64)
    rate = 32e6 ** (-np.arange(0, 16, 2) / 16)
    turned = (z[..., 0] + 1j * z[..., 1]) * np.exp(
        1j * positions[..., None] * rate)[:, :, None, :]
    want = np.stack([turned.real, turned.imag], axis=-1).reshape(x.shape)
    got = _rope(jnp.asarray(x), jnp.asarray(positions), 32e6, True)
    assert _rel(got, want) <= 2e-6
    assert _rel(plain.rotate(jnp.asarray(x[0]), 32e6), want[0]) <= 2e-6
    k = rng.standard_normal((2, 64, 3, 16)).astype(np.float32)
    scores = [jnp.einsum("bqhd,bkhd->bhqk",
                         _rope(jnp.asarray(x), positions + shift, 32e6, True),
                         _rope(jnp.asarray(k), positions + shift, 32e6, True))
              for shift in (0, 5)]
    assert _rel(scores[1], scores[0]) <= 1e-5
    # and it is not the other pairing: half against half is 1 away
    assert _rel(_rope(jnp.asarray(x), positions, 32e6), want) > 0.1


def test_rope_default_is_unchanged():
    """Ouro's and Keye's form, bit for bit in values, and lowered to the
    same program (source locations apart)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 4, 16))
    positions = jnp.broadcast_to(jnp.arange(32), (2, 32))
    for dtype in (jnp.float32, jnp.bfloat16):
        assert np.array_equal(
            np.asarray(_rope(x.astype(dtype), positions, 1e4), np.float32),
            np.asarray(_half_split(x.astype(dtype), positions, 1e4),
                       np.float32))
    texts = [jax.jit(lambda x, f=f: f(x, positions, 1e4)).lower(x).as_text()
             for f in (_rope, _half_split)]
    assert texts[0] == texts[1]


# --------------------------------------------------------------------------
# latent attention

class _KimiLatentAsOf39(nn.Module):
    """Kimi-Linear's layer as PR 39 wrote it: one query projection and no
    rotation."""

    heads: int = 4
    nope_dim: int = 16
    rope_dim: int = 8
    v_dim: int = 16
    kv_rank: int = 32

    @nn.compact
    def __call__(self, x):
        b, s, d_model = x.shape
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=jnp.float32, name=name)
        qk_dim = self.nope_dim + self.rope_dim
        q = dense(self.heads * qk_dim, "q")(x).reshape(
            b, s, self.heads, qk_dim)
        c = dense(self.kv_rank + self.rope_dim, "kv_a")(x)
        shared = jnp.broadcast_to(c[:, :, None, self.kv_rank:],
                                  (b, s, self.heads, self.rope_dim))
        kv = dense(self.heads * (self.nope_dim + self.v_dim), "kv_b")(
            RMSNorm(1e-5, name="kv_norm")(c[..., :self.kv_rank])
        ).reshape(b, s, self.heads, self.nope_dim + self.v_dim)
        k = jnp.concatenate([kv[..., :self.nope_dim], shared], axis=-1)
        out = full_attention(q, k, kv[..., self.nope_dim:], causal=True,
                             scale=qk_dim ** -0.5)
        return dense(d_model, "o")(out.reshape(b, s, self.heads * self.v_dim))


def test_latent_attention_without_rank_and_rotation_is_kimi_linear_s():
    """Bit for bit, values and every gradient, over the same tree."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64))
    new = KimiLatentAttention(4, 16, 8, 16, 32, jnp.float32)
    old = _KimiLatentAsOf39()
    params = new.init(jax.random.PRNGKey(0), x)
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(old.init(jax.random.PRNGKey(0),
                                                     x)))
    got, want = (jax.value_and_grad(
        lambda p, x, m=m: (m.apply(p, x) ** 2).sum(), argnums=(0, 1))(
            params, x) for m in (new, old))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_latent_attention_with_rank_and_rotation_is_the_plain_layer():
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 48, 64))
    layer = KimiLatentAttention(4, 16, 8, 16, 32, jnp.float32, 1e-6, 48, 32e6)
    params = layer.init(jax.random.PRNGKey(0), x)
    assert set(params["params"]) == {"q_a", "q_norm", "q_b", "kv_a",
                                     "kv_norm", "kv_b", "o"}
    want = jax.vmap(lambda row: plain._mla(
        row, params["params"], heads=4, kv_rank=32, v_dim=16, rope_dim=8,
        theta=32e6, dtype=jnp.float32, eps=1e-6, query_block=16,
        rope_dtype=jnp.float32))(x)
    assert _rel(layer.apply(params, x), want) <= 1e-5


# --------------------------------------------------------------------------
# the model against the plain reference

@pytest.mark.parametrize("rows", (1, 2))
def test_model_loss_terms_and_gradients_are_the_plain_reference_s(rows):
    """A dense layer, two expert layers and the MTP module (keys 24 wide,
    values 16). Each term alone per position, the sum, and every gradient
    leaf — the embedding's and the head's included."""
    model, params, tokens = _model_and_params(rows)
    main, mtp = model.apply(params, tokens)
    want_main, want_mtp = plain.causal_lm_nll(params, tokens, **PLAIN)
    assert main.shape == (rows, 63) and mtp.shape == (rows, 62)
    assert _rel(main, want_main) <= 1e-6 and _rel(mtp, want_mtp) <= 1e-6
    got, want = (jax.jit(jax.value_and_grad(f))(params) for f in (
        lambda p: joyai_loss(model.apply(p, tokens)),
        lambda p: _plain_loss(p, tokens)))
    assert abs(float(got[0]) - float(want[0])) <= 1e-6 * float(want[0])
    assert abs(float(got[0]) - float(want_main.mean()
                                     + LAMBDA * want_mtp.mean())) <= 1e-5
    flat = jax.tree_util.tree_leaves_with_path(got[1])
    reached = 0
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want[1])):
        name = jax.tree_util.keystr(path)
        if "select_bias" in name:       # the loss never reaches the bias
            assert not bool(g.any()) and not bool(w.any())
            continue
        assert _rel(g, w) <= 1e-5, name
        reached += bool(w.any())
    assert reached == len(flat) - 3     # every other leaf has a gradient


def test_shared_leaves_carry_the_sum_of_both_paths():
    """One embedding leaf and one head leaf, each used by both streams:
    the program's gradient is the main term's plus the MTP term's. A
    detached MTP embedding or a second head leaf (the module reading a
    tree no gradient returns from) is 1e-2 and more away."""
    model, params, tokens = _model_and_params()
    got = jax.grad(lambda p: joyai_loss(model.apply(p, tokens)))(params)
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(params))
    detached = jax.grad(lambda p: _plain_loss(
        p, tokens, mtp_leaves=jax.lax.stop_gradient(p)))(params)
    main_path, mtp_path = (
        jax.grad(lambda p, w=w: _plain_loss(p, tokens, *w))(params)
        for w in ((1.0, 0.0), (0.0, LAMBDA)))
    for module, leaf in (("embed", "embedding"), ("lm_head", "kernel")):
        g, d, a, b = (tree["params"][module][leaf]
                      for tree in (got, detached, main_path, mtp_path))
        assert _rel(g, a + b) <= 1e-5
        assert _rel(g, d) > 1e-2 and _rel(g, a) > 1e-2
    # what is detached is those two leaves' second path and nothing else
    assert _rel(got["params"]["mtp"]["eh_proj"]["kernel"],
                detached["params"]["mtp"]["eh_proj"]["kernel"]) <= 1e-5


def test_mtp_targets_are_two_ahead():
    """Row i of the second stream is scored on token i + 2. Change the last
    token alone: it is the target of main row s - 2 and of MTP row s - 3,
    and the input of no row that is kept (a shift of one would make it
    the target of MTP row s - 2, which does not exist: nothing would
    move). And the stream is the reference's, whose shifts are written
    out; the same rows scored one ahead are 0.1 and more away."""
    model, params, tokens = _model_and_params(1)
    other = tokens.copy()
    other[0, -1] = (tokens[0, -1] + 7) % 512
    (main, mtp), (main2, mtp2) = (model.apply(params, t)
                                  for t in (tokens, other))
    moved = np.flatnonzero(np.asarray(mtp != mtp2)[0])
    assert moved.tolist() == [61]
    assert np.flatnonzero(np.asarray(main != main2)[0]).tolist() == [62]
    want = plain.causal_lm_nll(params, tokens, **PLAIN)[1]
    one_ahead = plain.causal_lm_nll(
        params, np.concatenate([tokens[:, :1], tokens], axis=1)[:, :-1],
        **PLAIN)[1]
    assert _rel(mtp, want) <= 1e-6 and _rel(mtp, one_ahead) > 0.1


@pytest.mark.parametrize("what", ("router", "rotation", "half_split"))
def test_the_tolerance_fails_a_bf16_router_or_rotation_or_the_other_pairs(
        what, monkeypatch):
    """What a bf16 router would see (its weights rounded), a rotation whose
    angles and products are bf16, and ``_rope``'s half-against-half pairs
    in the mixers: each moves a position's loss by at least ten times the
    1e-6 both streams are held to."""
    import byteps_tpu.models.kimi_linear as kimi_linear

    model, params, tokens = _model_and_params()
    over, seen = {}, params
    if what == "router":
        seen = jax.tree_util.tree_map_with_path(
            lambda path, x: x.astype(jnp.bfloat16).astype(jnp.float32)
            if "router" in jax.tree_util.keystr(path) else x, params)
    elif what == "rotation":
        over = {"rope_dtype": jnp.bfloat16}
    else:
        monkeypatch.setattr(
            kimi_linear, "_rope", lambda x, positions, theta, interleaved:
            _rope(x, positions, theta))
    got = model.apply(params, tokens)
    want = plain.causal_lm_nll(seen, tokens, **{**PLAIN, **over})
    assert max(map(_rel, got, want)) > 1e-5


# --------------------------------------------------------------------------
# the share

def test_the_32_shares_parts_add_up_with_the_shared_expert_counted_once():
    """The model-configs guide's test at the deployment's count: 32 chips
    hold two of 64 experts each (top 8, routed scale 2.5); each computes its
    experts' part and the shared expert whole. The 32 outputs less 31 copies
    of the shared expert's are the uncut layer's (``plain_kimi_linear.
    experts`` holding all 64)."""
    T, D, M, E, K = 48, 32, 24, 64, 8
    rng = np.random.default_rng(0)

    def normal(*shape, scale=1.0):
        return jnp.asarray((rng.standard_normal(shape) * scale).astype(
            np.float32))

    x, wr = normal(T, D), normal(D, E, scale=0.5)
    wg, wu, wd = (normal(E, D, M, scale=D ** -0.5),
                  normal(E, D, M, scale=D ** -0.5),
                  normal(E, M, D, scale=D ** -0.5))
    shared = {name: {"kernel": normal(*shape, scale=shape[0] ** -0.5)}
              for name, shape in (("gate", (D, M)), ("up", (D, M)),
                                  ("down", (M, D)))}
    bias = normal(E, scale=0.1)
    total = 0.0
    for first in range(0, E, 2):
        layer = KimiSparseMoe(E, 2, first, K, M, 2.5, dtype=jnp.float32)
        total = total + layer.apply({"params": {
            "router": wr, "select_bias": bias, "shared": shared,
            **{name: w[first:first + 2] for name, w in
               (("gate", wg), ("up", wu), ("down", wd))}}}, x[None])[0]
    alone = plain_kimi._swiglu(x, shared, jnp.float32)
    uncut = plain_kimi.experts(
        x, {"router": wr, "select_bias": bias, "gate": wg, "up": wu,
            "down": wd, "shared": shared}, top_k=K, first_expert=0,
        routed_scale=2.5, dtype=jnp.float32)
    assert _rel(total - 31 * alone, uncut) <= 1e-5


# --------------------------------------------------------------------------
# the configuration's arithmetic

def _config():
    return (cell_lib.load_json(CONFIG + ".json"),
            cell_lib.load_module(CONFIG + ".py", "joyai_config"))


def test_parameter_count_by_hand():
    """The docstring of the configuration's ``.py``, and the published
    model: 48.9 B without the MTP module, 50.2 B with it; a token meets 2.77
    B of the layers (the name's 48B-A2.7B; 3.30 B with the embedding and the
    head). The embedding and the head are counted once each, though two
    streams use them."""
    mla = (2048 * 1536 + 1536 + 1536 * 6144 + 2048 * 576 + 512 + 512 * 8192
           + 4096 * 2048)
    expert, router, norms = 3 * 2048 * 768, 2048 * 256 + 256, 2 * 2048
    assert (mla, expert, router) == (26_347_520, 4_718_592, 524_544)
    dense = mla + norms + 3 * 2048 * 7168
    held = mla + norms + router + 9 * expert
    ends = 2 * 16_160 * 2048 + 2048
    module = held + 2 * 2048 * 2048 + 3 * 2048
    assert (dense, held, ends, module) == (70_391_808, 69_343_488,
                                           66_193_408, 77_738_240)
    cfg, config = _config()
    assert cfg["n_params"] == dense + 4 * held + ends + module \
        == 491_697_408
    init, _ = config.build(cfg)
    shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves_with_path(shapes)
    assert sum(math.prod(x.shape) for _, x in leaves) == cfg["n_params"]
    for leaf in ("embedding", "lm_head"):
        assert sum(leaf in jax.tree_util.keystr(path)
                   for path, _ in leaves) == 1
    whole = mla + norms + router + 257 * expert
    assert whole == 1_239_554_304       # ISSUE 41: 31,594,752 + 256 experts
    main = dense + 39 * whole + 2 * 129_280 * 2048 + 2048
    with_module = main + whole + 2 * 2048 * 2048 + 3 * 2048
    active = main - 39 * 248 * expert
    assert round(main / 1e9, 1) == 48.9
    assert round(with_module / 1e9, 1) == 50.2
    assert round(active / 1e9, 2) == 3.30
    assert round((active - 2 * 129_280 * 2048) / 1e9, 2) == 2.77
    published = JoyAIFlash48BA3B()
    assert (published.num_layers, published.num_local_experts,
            published.vocab_size) == (40, 256, 129_280)


def test_flops_per_token_by_hand():
    """Rows that carry a target, and no others: the main head at s - 1 rows
    of s, the module at s - 2 (its two padded rows earn nothing)."""
    cfg, config = _config()
    mla = 26_347_520 - 1536 - 512
    moe = 524_288 + 1_179_648 + 4_718_592
    head, pair, s = 2048 * 16_160, 6 * (192 + 128) * 32, 8_192
    assert (mla, moe, head, pair) == (26_345_472, 6_422_528, 33_095_680,
                                      61_440)
    main = 6 * (5 * mla + 44_040_192 + 4 * moe)
    module = 6 * (2 * 2048 * 2048 + mla + moe + head)
    assert (main, module) == (1_208_745_984, 445_513_728)
    sequence = (s * main + 5 * pair * s * (s + 1) // 2 + (s - 1) * 6 * head
                + (s - 2) * module + pair * (s - 2) * (s - 1) // 2)
    assert config.flops_per_token(cfg) == sequence // s == 3_362_711_671
    # ISSUE 41's count has both heads and the module at all s rows
    issue = (main + 6 * head + 5 * 251_688_960) + (module + 251_688_960)
    assert issue == 2_665_764_864 + 697_202_688 == 3_362_967_552
    assert issue - 3_362_711_671 == 255_881
    pairs = 5 * pair * s * (s + 1) // 2 + pair * (s - 2) * (s - 1) // 2
    assert round(100 * pairs / sequence) == 45
    half = config.flops_per_token({**cfg, "seq_len": 4_096})
    assert half < 3_362_711_671 - 0.2 * 3_362_711_671  # half the attention


# --------------------------------------------------------------------------
# tracing, and the normal path

def test_stats_are_sown_only_when_asked_for_and_published():
    model, params, tokens = _model_and_params()
    out = model.apply(params, tokens)
    assert isinstance(out, tuple) and len(out) == 2
    (main, mtp), stats = model.apply(params, tokens,
                                     mutable=["moe_stats", "mtp_stats"])
    counts = jax.tree_util.tree_leaves(stats["moe_stats"])
    assert len(counts) == 3 and all(int(c.sum()) == 2 * 64 * 2
                                    for c in counts)   # the module's too
    published = publish_mtp_stats(stats["mtp_stats"])
    assert published == {"bps_mtp_main_loss": float(main.mean()),
                         "bps_mtp_next2_loss": float(mtp.mean())}
    assert metrics._py_gauges["bps_mtp_next2_loss"] == float(mtp.mean())
    assert publish_mtp_stats({}) == {}


def test_scopes_nest_in_the_compiled_program():
    """Each span of the tracing is in the compiled program's ``op_name``s
    (what the device trace's ``tf_op`` holds), forward and backward; the
    module's block and head carry ``bps.mtp`` over their own scopes, the
    main stack's do not. Compiled, not lowered: a head block is a call
    inside a scan, and only the compiler joins a callee's names to its
    caller's. The routed experts' backward pass is ``jax.vjp`` of a pass
    inside a ``custom_vjp`` rule (PR 43), which writes the scope inside the
    transformation — ``transpose(jvp(bps.moe.experts))`` — and the readers
    match a scope anywhere in a name."""
    import re

    model, params, tokens = _model_and_params(1)
    names = set(re.findall(r'op_name="([^"]*)"', jax.jit(jax.value_and_grad(
        lambda p: joyai_loss(model.apply(p, tokens)))).lower(
            params).compile().as_text()))
    for scope in ("bps.mla.attend", "bps.mla.proj", "bps.mtp",
                  "bps.mtp.combine", "bps.moe.shared", "bps.moe.route",
                  "bps.moe.experts", "bps.lm.head"):
        for pass_ in ("/jvp(", "/transpose(jvp("):
            assert any((f"/{scope}/" in n or f"({scope})" in n) and pass_ in n
                       for n in names), (scope, pass_)
    for inner in ("bps.mla.attend", "bps.mla.proj", "bps.moe.route",
                  "bps.lm.head"):
        for inside in (True, False):
            assert any(("/bps.mtp/" in n) == inside and f"/{inner}/" in n
                       for n in names), (inner, inside)
    assert not any("/bps.mtp.combine/" in n and "/bps.mtp/" not in n
                   for n in names)


def test_the_model_trains_through_make_train_step_on_the_mesh():
    """bps.init() -> make_train_step(loss_fn, adamw) -> step on 8 virtual
    chips: the first loss is the single-device loss of the same batch and
    the loss falls."""
    import byteps_tpu.jax as bps
    from byteps_tpu.jax.training import (make_train_step, replicate,
                                         shard_batch)

    model, params, tokens = _model_and_params(8, 32)

    def loss_fn(p, batch):
        return joyai_loss(model.apply(p, batch["tokens"]))

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
    assert importlib.import_module("benchmark.lib.plain_joyai") is plain

"""KeyeModel and the expert layer told its share (tier-1, CPU, seeded).

Yardsticks that share no code with the program: ``benchmark/lib/
plain_keye.py`` for the model and, for the share, its ``_experts`` holding
ALL experts — the uncut layer that the shares' parts must add up to. In
float32 on the CPU both sides differ by the order sums are taken in (a
relative 1e-5 of the largest entry; 1e-4 through two layers' gradients).
"""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.models import Keye30BA3B, KeyeTiny, keye_loss
import byteps_tpu.parallel.moe as moe
from byteps_tpu.parallel.moe import (dropless_moe_ffn, held_row_bound,
                                     publish_moe_stats)
from byteps_tpu.parallel.sparse_attention import publish_dsa_stats

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.lib import cell as cell_lib  # noqa: E402
from benchmark.lib import plain_keye  # noqa: E402

T, D, M, E = 48, 32, 24, 8


def _close(got, want, rtol=1e-5):
    scale = float(jnp.abs(want).max())
    return float(jnp.abs(got - want).max()) <= rtol * max(scale, 1e-30)


def _layer_inputs(skew, seed=0, t=T):
    """Seeded tokens and all E experts' weights. ``skew``: that share of the
    tokens has 0 and 1 as its two best experts (a feature column only their
    router columns read) — at 1 one share of two receives every assignment
    — and the other tokens have them as their two worst."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((t, D)).astype(np.float32)
    wr = rng.standard_normal((D, E)).astype(np.float32) * 0.5
    if skew:
        x[:, 0] = np.where(np.arange(t) < skew * t, 8.0, -8.0)
        wr[0, :] = 0.0
        wr[0, :2] = 4.0
    scale = 1.0 / math.sqrt(D)
    return tuple(jnp.asarray(a) for a in (
        x, wr,
        rng.standard_normal((E, D, M)).astype(np.float32) * scale,
        rng.standard_normal((E, D, M)).astype(np.float32) * scale,
        rng.standard_normal((E, M, D)).astype(np.float32) * scale))


def _share(x, wr, wg, wu, wd, first, held, top_k=2, **gate):
    return dropless_moe_ffn(
        x, wr, *(w[first:first + held] for w in (wg, wu, wd)), top_k=top_k,
        dtype=jnp.float32, first_expert=first, norm_topk=True, **gate)


# 48 tokens: 96 rows, under one multiple of the bound, so a pass is every
# row. 512 tokens: 1,024 rows in passes of 512 — even routing fits one pass
# in every share; with three quarters of the tokens on experts 0 and 1 the
# first share (768 rows) takes two and the others one.
@pytest.mark.parametrize("skew,t", ((0, T), (1, T), (0, 512), (0.75, 512)))
def test_the_shares_parts_add_up_to_the_uncut_layer(skew, t):
    """E = 8 in 4 shares of 2: what the shares compute, each for its own
    experts, sums to the plain reference's layer with all experts held —
    values and gradients — and each share alone is the reference given the
    same share. Dropless under any routing: with ``skew`` 1 one share gets
    all T k assignments and the others none."""
    args = _layer_inputs(skew, t=t)
    uncut = {"router": args[1], "gate": args[2], "up": args[3],
             "down": args[4]}
    with jax.default_matmul_precision("highest"):
        want, want_lb = plain_keye._experts(args[0], uncut, 2, 0, jnp.float32)
    parts = [_share(*args, first, 2) for first in range(0, E, 2)]
    assert _close(sum(p[0] for p in parts), want)
    for y, load_balance, _, counts in parts:
        assert abs(float(load_balance) - float(want_lb)) <= 1e-5
        assert int(counts.sum()) == t * 2       # counted over all experts
    if skew == 1:
        assert list(np.asarray(parts[0][3])) == [t, t, 0, 0, 0, 0, 0, 0]
        assert not np.asarray(parts[1][0]).any()
    if t == 512:
        fits = [int(parts[0][3][first:first + 2].sum())
                <= held_row_bound(t, 2, 2, E) == 512
                for first in range(0, E, 2)]
        assert fits == [not skew, True, True, True]
    for first in range(0, E, 2):
        held = {k: v if k == "router" else v[first:first + 2]
                for k, v in uncut.items()}
        with jax.default_matmul_precision("highest"):
            alone, _ = plain_keye._experts(args[0], held, 2, first,
                                           jnp.float32)
        assert _close(parts[first // 2][0], alone)

    cot = jnp.asarray(np.random.default_rng(1).standard_normal(
        (t, D)).astype(np.float32))

    def summed(*a):
        return sum((_share(*a, first, 2)[0] * cot).sum()
                   for first in range(0, E, 2))

    def reference(x, wr, wg, wu, wd):
        with jax.default_matmul_precision("highest"):
            y, _ = plain_keye._experts(x, {"router": wr, "gate": wg,
                                           "up": wu, "down": wd}, 2, 0,
                                       jnp.float32)
        return (y * cot).sum()

    got_g = jax.grad(summed, argnums=range(5))(*args)
    want_g = jax.grad(reference, argnums=range(5))(*args)
    for name, got, want_leaf in zip(("x", "router", "gate", "up", "down"),
                                    got_g, want_g):
        assert _close(got, want_leaf, rtol=3e-5), name


@pytest.mark.parametrize("first", (0, 4))
@pytest.mark.parametrize("scoring", ("softmax", "sigmoid"))
def test_one_pass_is_the_layer_in_several_passes(monkeypatch, scoring, first):
    """The same 512 tokens through a share of 2 of 8 experts in one pass of
    512 of the 1,024 rows, then with a pass held to 128 rows, under what
    the share receives: two to four passes. ``y``, the counts, both losses
    and the gradients of x, the router and the three weights agree to 1e-6
    of a leaf's largest entry: the same float32 products, a token's sum and
    a weight's gradient taken in another order."""
    args = _layer_inputs(0, t=512)
    gate = dict(scoring=scoring)
    if scoring == "sigmoid":
        gate.update(norm_eps=1e-20, routed_scale=2.446, select_bias=jnp.where(
            jnp.arange(E) == 5, 0.3, 0.0))
    cot = jnp.asarray(np.random.default_rng(1).standard_normal(
        (512, D)).astype(np.float32))

    def both(*a):
        y, load_balance, z_loss, counts = _share(*a, first, 2, **gate)
        return (y * cot).sum() + load_balance + z_loss, (y, load_balance,
                                                         z_loss, counts)

    run = jax.jit(jax.value_and_grad(both, argnums=range(5), has_aux=True))
    (_, one_pass), one_pass_g = run(*args)
    received = int(one_pass[3][first:first + 2].sum())
    assert 128 < received <= held_row_bound(512, 2, 2, E) == 512
    text = run.lower(*args).as_text()
    # forward and backward: one pass, or a loop of them
    assert text.count("stablehlo.case") == text.count("stablehlo.while") == 2
    monkeypatch.setattr(moe, "held_row_bound", lambda *shape: 128)
    (_, passes), passes_g = jax.jit(jax.value_and_grad(
        both, argnums=range(5), has_aux=True))(*args)
    assert np.array_equal(np.asarray(one_pass[3]), np.asarray(passes[3]))
    for got, want in zip(one_pass[:3] + one_pass_g, passes[:3] + passes_g):
        assert _close(got, want, rtol=1e-6)


def _routed_to_held(held_rows, t=512):
    """Tokens and a router that send exactly ``held_rows`` of the t * 2
    assignments to experts 0 and 1: both choices of the first
    ``held_rows // 2`` tokens, one of the next token's if the number is odd,
    none of the others'."""
    args = list(_layer_inputs(0, t=t))
    x, wr = np.array(args[0]), np.array(args[1])
    both, one = held_rows // 2, held_rows % 2
    x[:, 0] = np.where(np.arange(t) < both, 8.0, -8.0)
    x[:, 1] = 0.0
    wr[:2, :] = 0.0
    wr[0, :2] = 4.0                         # column 0: experts 0 and 1, or not
    if one:
        x[both, :2] = (0.0, 8.0)
        wr[1, :2] = (4.0, -4.0)             # column 1: expert 0 and not 1
    return [jnp.asarray(x), jnp.asarray(wr)] + args[2:]


@pytest.mark.parametrize("held_rows", (512, 513, 1024))
def test_nothing_is_dropped_at_the_bound_and_beyond_it(held_rows):
    """A routing built to put exactly the bound (512 of 1,024 rows: one
    pass), one assignment more (a second pass for it), and every assignment
    (all T k: each token's two best experts are the held two) on the held
    experts: the share's output and gradients are the plain reference's, so
    no row was left out at the edge of a pass or beyond the first."""
    args = _routed_to_held(held_rows)
    assert held_row_bound(512, 2, 2, E) == 512
    y, _, _, counts = jax.jit(lambda *a: _share(*a, 0, 2))(*args)
    assert int(counts[:2].sum()) == held_rows
    held = [args[1]] + [w[:2] for w in args[2:]]
    cot = jnp.asarray(np.random.default_rng(1).standard_normal(
        (512, D)).astype(np.float32))

    def reference(x, wr, wg, wu, wd):
        with jax.default_matmul_precision("highest"):
            return plain_keye._experts(x, {"router": wr, "gate": wg, "up": wu,
                                           "down": wd}, 2, 0, jnp.float32)[0]

    assert _close(y, reference(args[0], *held))
    got_g = jax.jit(jax.grad(lambda x, wr, wg, wu, wd: (dropless_moe_ffn(
        x, wr, wg, wu, wd, top_k=2, dtype=jnp.float32, first_expert=0,
        norm_topk=True)[0] * cot).sum(), argnums=range(5)))(args[0], *held)
    want_g = jax.grad(lambda *a: (reference(*a) * cot).sum(),
                      argnums=range(5))(args[0], *held)
    for name, got, want in zip(("x", "router", "gate", "up", "down"),
                               got_g, want_g):
        assert _close(got, want, rtol=3e-5), name


@pytest.mark.parametrize("shape,bound", (
    ((8192, 8, 8, 256), 4096),        # Kimi-Linear's and JoyAI's cells
    ((8192, 8, 16, 128), 16384),      # Keye's
    ((8192, 8, 64, 64), 65536),       # OLMoE's: every expert, every row
    ((8192, 8, 64, 128), 65536),      # half the experts: twice the even part
    ((512, 2, 2, 8), 512), ((48, 2, 2, 8), 96),     # these tests'
    ((1000, 3, 5, 64), 512), ((1000, 3, 7, 64), 1024),      # in 512s, up
))
def test_the_row_bound_by_hand(shape, bound):
    assert held_row_bound(*shape) == bound


def test_the_compact_share_counts_the_layers_that_fit_the_bound():
    """Made-up counts of three layers over 8 experts, 1,024 assignments
    each, experts 2 and 3 held (bound 512): 512 and 100 fit, 513 does
    not."""
    from byteps_tpu.monitor import metrics

    def layer(held_rows):
        counts = np.zeros(8, np.int64)
        counts[2], counts[3] = held_rows - 40, 40
        counts[7] = 1024 - held_rows
        return counts

    out = publish_moe_stats([layer(512), layer(100), layer(513)], held=(2, 2))
    assert out["bps_moe_compact_share"] == pytest.approx(2 / 3)
    assert metrics._py_gauges["bps_moe_compact_share"] == pytest.approx(2 / 3)
    assert out["bps_moe_held_load"] == pytest.approx(1125 / 768)
    assert publish_moe_stats([layer(100)], held=(2, 2))[
        "bps_moe_compact_share"] == 1.0
    # half the experts held: a pass is every row, and always enough
    assert publish_moe_stats([layer(1024)], held=(0, 4))[
        "bps_moe_compact_share"] == 1.0
    assert "bps_moe_compact_share" not in publish_moe_stats([layer(100)])


def test_holding_every_expert_is_the_layer_olmoe_calls_bit_for_bit():
    args = _layer_inputs(False)
    for dtype in (jnp.float32, jnp.bfloat16):
        as_olmoe = dropless_moe_ffn(*args, top_k=2, dtype=dtype)
        told = dropless_moe_ffn(*args, top_k=2, dtype=dtype, first_expert=0,
                                norm_topk=False)
        for a, b in zip(as_olmoe, told):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    # renormalised weights are another layer
    normed = dropless_moe_ffn(*args, top_k=2, dtype=jnp.float32,
                              norm_topk=True)
    assert not _close(normed[0].astype(jnp.float32),
                      as_olmoe[0].astype(jnp.float32), rtol=2e-2)
    with pytest.raises(ValueError, match="not among"):
        dropless_moe_ffn(args[0], args[1], *(w[:2] for w in args[2:]),
                         top_k=2, first_expert=7)


def _config():
    path = os.path.join(REPO, "benchmark", "configs", "keye-vl-2.0-30b-a3b")
    return (cell_lib.load_json(path + ".json"),
            cell_lib.load_module(path + ".py", "cfg_keye"))


@pytest.mark.parametrize("rows", (1, 2))
def test_model_loss_and_gradients_are_the_plain_reference_s(rows):
    """KeyeModel + keye_loss against benchmark/lib/plain_keye.py at the
    rehearsal size (2 layers, 2 of 8 experts held, 64 tokens selecting 16
    keys), float32."""
    cfg, module = _config()
    cfg = {**cfg, **cfg["rehearsal_sizing"]}
    assert cfg["seq_len"] > cfg["sa_config"]["topk"]
    init, loss_fn = module.build(cfg)
    params = init(jax.random.PRNGKey(3))
    batch = module.make_batch(cfg, np.random.default_rng(3), rows)
    weighted = {**batch, "weight": module.reference_weights(cfg, batch, 1)}
    got, got_g = jax.jit(jax.value_and_grad(loss_fn))(params, batch)
    want, want_g = jax.jit(jax.value_and_grad(module.reference_loss(cfg)))(
        params, weighted)
    assert abs(float(got) - float(want)) <= 2e-6 * float(want)
    flat = jax.tree_util.tree_flatten_with_path(want_g)[0]
    assert len(flat) == len(jax.tree_util.tree_leaves(got_g)) == 37
    for (path, want_leaf), got_leaf in zip(
            flat, jax.tree_util.tree_leaves(got_g)):
        assert _close(got_leaf, want_leaf, rtol=1e-4), \
            jax.tree_util.keystr(path)


def test_each_loss_trains_its_own_leaves_and_no_other():
    """The language-model loss (with the load-balancing term) leaves zero
    gradient on every indexer leaf; the indexer's loss leaves zero on every
    other leaf and a gradient on each of its own."""
    model = KeyeTiny(dtype=jnp.float32)
    tokens = np.random.default_rng(0).integers(0, 512, (2, 64),
                                               dtype=np.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)

    def grads(loss):
        return jax.tree_util.tree_flatten_with_path(
            jax.jit(jax.grad(loss))(params))[0]

    lm = grads(lambda p: keye_loss(model.apply(p, tokens), tokens,
                                   index_loss_weight=0.0))
    index = grads(lambda p: model.apply(p, tokens)[1]["index_loss"])
    indexer = 0
    for (path, lm), (_, index) in zip(lm, index):
        name = jax.tree_util.keystr(path)
        if "indexer" in name:
            indexer += 1
            assert not np.asarray(lm).any(), name
            assert np.asarray(index).any(), name
        else:
            assert not np.asarray(index).any(), name
            assert np.asarray(lm).any(), name
    assert indexer == 2 * 5       # q, k, w, and the LayerNorm's two, a layer


def test_parameter_count_by_hand():
    attention = 2048 * 4096 * 2 + 2048 * 512 * 2 + 2 * 128
    indexer = 2048 * (1024 + 64 + 16) + 2 * 64
    outside = attention + indexer + 2048 * 128 + 2 * 2048
    assert (attention, indexer, outside) == (18_874_624, 2_261_120,
                                             21_401_984)
    expert = 3 * 2048 * 768
    assert outside + 128 * expert == 625_381_760       # a published layer
    assert outside + 16 * expert == 96_899_456         # a cut layer
    published = 48 * 625_381_760 + 2 * 151_936 * 2048 + 2048
    assert published == 30_640_656_384                 # the "30B"

    def count(model):
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                                np.zeros((1, 8), np.int32))
        assert list(shapes) == ["params"]
        return sum(math.prod(leaf.shape)
                   for leaf in jax.tree_util.tree_leaves(shapes))

    assert count(Keye30BA3B()) == published
    cfg, module = _config()
    cut = count(Keye30BA3B(num_layers=cfg["num_hidden_layers"],
                           num_local_experts=16, vocab_size=18_992))
    assert cut == cfg["n_params"] == (cfg["num_hidden_layers"] * 96_899_456
                                      + 2 * 18_992 * 2048 + 2048)
    assert 4 <= cfg["num_hidden_layers"] <= 6
    assert 151_936 // 8 == 18_992 == cfg["vocab_size"]


def test_flops_per_token_by_hand():
    cfg, module = _config()
    assert module.attended_pairs(8192, 2048) == (14_681_088, 33_558_528)
    assert module.attended_pairs(2048, 2048) == (2_098_176, 2_098_176)
    matmul = 18_874_368 + 2048 * 128 + 1 * 3 * 2048 * 768   # one held expert
    assert matmul == 23_855_104
    attention = (12 * 32 * 128 * 14_681_088 + 6 * 16 * 64 * 33_558_528) // 8192
    assert attention == 113_255_424
    layer = 6 * matmul + 4 * 2_260_992 + attention
    assert layer == 265_430_016
    head = 6 * 2048 * 18_992
    for layers, want in ((4, 1_295_093_760), (6, 1_825_953_792)):
        assert module.flops_per_token(
            {**cfg, "num_hidden_layers": layers}) == layers * layer + head \
            == want
    six = 1_825_953_792
    assert round(100 * 6 * (4 * 2_260_992 + attention) / six) == 40
    assert round(100 * 6 * 6 * 18_874_368 / six) == 37
    assert round(100 * 6 * 6 * (2048 * 128 + 3 * 2048 * 768) / six) == 10
    assert round(100 * head / six) == 13


def test_counts_are_sown_only_when_asked_for_and_published():
    from byteps_tpu.monitor import metrics

    model = KeyeTiny(dtype=jnp.float32)
    tokens = np.random.default_rng(0).integers(0, 512, (2, 64),
                                               dtype=np.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)
    assert list(params) == ["params"]
    logits, aux = jax.jit(model.apply)(params, tokens)     # nothing mutable
    assert logits.shape == (2, 64, 512) and logits.dtype == jnp.float32
    assert set(aux) == {"load_balance", "index_loss"}
    (_, _), stats = jax.jit(lambda p: model.apply(
        p, tokens, mutable=["moe_stats", "dsa_stats"]))(params)
    counts = jax.tree_util.tree_leaves(stats["moe_stats"])
    assert len(counts) == 2 and all(c.shape == (8,) and
                                    int(c.sum()) == 2 * 64 * 2
                                    for c in counts)
    published = publish_moe_stats(stats["moe_stats"], held=(0, 2))
    held = sum(int(c[:2].sum()) for c in counts)
    assert published["bps_moe_held_load"] == pytest.approx(
        held / (2 * 256 * 2 / 8))
    assert metrics._py_gauges["bps_moe_held_load"] == \
        published["bps_moe_held_load"]
    assert "bps_moe_held_load" not in publish_moe_stats(stats["moe_stats"])
    kept = publish_dsa_stats(stats["dsa_stats"])
    by_hand = sum(min(t + 1, 16) for t in range(64))
    assert kept["bps_dsa_selected_keys_total"] == 2 * 2 * by_hand
    assert kept["bps_dsa_kept_keys_ratio"] == by_hand / (64 * 65 // 2)
    # the expert layer recomputed is the same model
    again = KeyeTiny(dtype=jnp.float32, remat_experts=True)
    loss = lambda m: lambda p: keye_loss(m.apply(p, tokens), tokens)  # noqa
    a, b = (jax.jit(jax.value_and_grad(loss(m)))(params)
            for m in (model, again))
    assert float(a[0]) == float(b[0])
    for x, y in zip(*(jax.tree_util.tree_leaves(g[1]) for g in (a, b))):
        assert _close(x, y, rtol=1e-6)


@pytest.mark.parametrize("shape,loops,digest", (
    ((2, 32), 16,
     "30123efc7a3f9036f9dd190365acdec3cbf897ea1f8bf2fedc58b16eb4e12471"),
    ((2, 256), 128,
     "2f5fe18e5b810f3122ada42a7c8c089bb7dff8ce8fc2174501cbbcd9928ac0dc"),
))
def test_the_gate_s_new_arguments_leave_the_lowered_step_as_it_was(
        shape, loops, digest):
    """PR 39 gave ``dropless_moe_ffn`` a scoring rule, a selection bias, an
    epsilon and a routed scale. At their defaults the gradient of
    KeyeTiny's loss (a share: experts 0..1 of 8) lowers to a pinned text.

    Re-pinned in PR 43 (the commit after ``e700ff8``), which changes a
    share's text by design: until then the digest was ``454cbe32…`` of the
    text at ``3f4a582`` (12 loops at 64 tokens, all sparse attention's;
    124 at 512; no ``case``). Each of the two expert layers now walks the
    held experts' sorted rows in passes, forward and backward: a choice
    between one pass and a loop of them each way, so four choices and four
    loops more at either size. At 64 tokens a pass is all 128 rows, at 512
    it is 512 of the 1,024. OLMoE's text (``tests/test_olmoe.py``) did not
    move."""
    import hashlib

    model, tokens = KeyeTiny(), np.zeros(shape, np.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    text = jax.jit(jax.grad(lambda p: keye_loss(
        model.apply(p, tokens), tokens))).lower(params).as_text()
    assert text.count("stablehlo.while") == loops
    assert text.count("stablehlo.case") == 4
    assert hashlib.sha256(text.encode()).hexdigest() == digest

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
from byteps_tpu.parallel.moe import dropless_moe_ffn, publish_moe_stats
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


def _layer_inputs(skew, seed=0):
    """Seeded tokens and all E experts' weights. ``skew``: every token's
    two best experts are 0 and 1 (a feature column only their router columns
    read), so one share of two receives every assignment."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((T, D)).astype(np.float32)
    wr = rng.standard_normal((D, E)).astype(np.float32) * 0.5
    if skew:
        x[:, 0] = 8.0
        wr[0, :] = 0.0
        wr[0, :2] = 4.0
    scale = 1.0 / math.sqrt(D)
    return tuple(jnp.asarray(a) for a in (
        x, wr,
        rng.standard_normal((E, D, M)).astype(np.float32) * scale,
        rng.standard_normal((E, D, M)).astype(np.float32) * scale,
        rng.standard_normal((E, M, D)).astype(np.float32) * scale))


def _share(x, wr, wg, wu, wd, first, held, top_k=2):
    return dropless_moe_ffn(
        x, wr, *(w[first:first + held] for w in (wg, wu, wd)), top_k=top_k,
        dtype=jnp.float32, first_expert=first, norm_topk=True)


@pytest.mark.parametrize("skew", (False, True))
def test_the_shares_parts_add_up_to_the_uncut_layer(skew):
    """E = 8 in 4 shares of 2: what the shares compute, each for its own
    experts, sums to the plain reference's layer with all experts held —
    values and gradients — and each share alone is the reference given the
    same share. Dropless under any routing: with ``skew`` one share gets
    all T k assignments and the others none."""
    args = _layer_inputs(skew)
    uncut = {"router": args[1], "gate": args[2], "up": args[3],
             "down": args[4]}
    with jax.default_matmul_precision("highest"):
        want, want_lb = plain_keye._experts(args[0], uncut, 2, 0, jnp.float32)
    parts = [_share(*args, first, 2) for first in range(0, E, 2)]
    assert _close(sum(p[0] for p in parts), want)
    for y, load_balance, _, counts in parts:
        assert abs(float(load_balance) - float(want_lb)) <= 1e-5
        assert int(counts.sum()) == T * 2       # counted over all experts
    if skew:
        assert list(np.asarray(parts[0][3])) == [T, T, 0, 0, 0, 0, 0, 0]
        assert not np.asarray(parts[1][0]).any()
    for first in range(0, E, 2):
        held = {k: v if k == "router" else v[first:first + 2]
                for k, v in uncut.items()}
        with jax.default_matmul_precision("highest"):
            alone, _ = plain_keye._experts(args[0], held, 2, first,
                                           jnp.float32)
        assert _close(parts[first // 2][0], alone)

    cot = jnp.asarray(np.random.default_rng(1).standard_normal(
        (T, D)).astype(np.float32))

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


def test_the_gate_s_new_arguments_leave_the_lowered_step_as_it_was():
    """PR 39 gave ``dropless_moe_ffn`` a scoring rule, a selection bias, an
    epsilon and a routed scale. At their defaults the gradient of
    KeyeTiny's loss (a share: experts 0..1 of 8) lowers to the text it
    lowered to at ``3f4a582``."""
    import hashlib

    model, tokens = KeyeTiny(), np.zeros((2, 32), np.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    text = jax.jit(jax.grad(lambda p: keye_loss(
        model.apply(p, tokens), tokens))).lower(params).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "454cbe32b2173f0eee3d720395bab9d48259ae3313ff10b06aa2fe36adde89e6")

"""``parallel/sparse_attention.py`` against a dense-mask computation written
out here (tier-1, CPU, float32, seeded).

The yardstick shares no code with the op: index scores for all (query, key)
pairs at once; the selection by a stable descending argsort of each query's
causal scores, first ``min(t + 1, topk)`` taken — "the highest, ties to the
earlier key" said the plain way, where the op thresholds on the ``topk``-th
value (an exact search over the float's bits) and counts ties; one masked
softmax over all keys; the KL term.
In float32 on the CPU the two differ by the order sums are taken in: a
relative 1e-5 of the largest entry.
"""

import re

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.parallel.ring_attention import full_attention
from byteps_tpu.parallel.sparse_attention import (_DIGIT, _kth_key, _ordered,
                                                  _select, publish_dsa_stats,
                                                  sparse_attention)

RTOL = 1e-5
HI = jax.lax.Precision.HIGHEST
B, DH, HI_HEADS, DI = 2, 8, 3, 4

# (sequence, topk, block, query heads, key-value heads, tied scores)
CASES = [
    (32, 32, 16, 4, 4, False),      # T = topk: causal attention, groups of 1
    (32, 64, 16, 8, 1, False),      # T < topk, one key-value head under 8
    (96, 32, 16, 4, 4, False),      # T > topk: two blocks a span of keys
    (96, 32, 16, 8, 1, False),
    (96, 32, 16, 4, 2, True),       # most index scores exactly equal
    (64, 16, 32, 4, 2, False),      # topk under the block
]


def _inputs(s, heads, kv_heads, tied, seed=0):
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return rng.standard_normal(shape).astype(np.float32)

    index_q, index_k = normal(B, s, HI_HEADS, DI), normal(B, s, DI)
    index_w = np.abs(normal(B, s, HI_HEADS)) * 0.3
    if tied:
        # ReLU makes exact zeros: every key but each eighth scores 0 for
        # every query, so the topk-th value is a tie that many keys share
        index_k = np.abs(index_k)
        index_q = -np.abs(index_q)
        index_q[:, :, 0, :] *= -1.0
        index_k[:, np.arange(s) % 8 != 0] *= 0.0
    return tuple(jnp.asarray(a) for a in (
        normal(B, s, heads, DH), normal(B, s, kv_heads, DH),
        normal(B, s, kv_heads, DH), index_q, index_k, index_w))


def _scores(index_q, index_k, index_w):
    dots = jnp.einsum("bqjd,bsd->bqjs", index_q, index_k, precision=HI)
    return jnp.einsum("bqjs,bqj->bqs", jax.nn.relu(dots), index_w,
                      precision=HI)


def _mask(score, topk):
    """[b, s, s] bool by sorting: the plain statement of the selection."""
    score = np.asarray(score)
    b, s, _ = score.shape
    keep = np.zeros((b, s, s), bool)
    for i in range(b):
        for t in range(s):
            order = np.argsort(-score[i, t, :t + 1], kind="stable")
            keep[i, t, order[:topk]] = True
    return keep


def _dense(q, k, v, index_q, index_k, index_w, topk):
    """(out, index_loss, selected per sequence) over all keys at once."""
    heads, kv_heads = q.shape[2], k.shape[2]
    score = _scores(index_q, index_k, index_w)
    keep = jnp.asarray(_mask(jax.lax.stop_gradient(score), topk))
    k, v = (jnp.repeat(a, heads // kv_heads, axis=2) for a in (k, v))
    logits = jnp.einsum("bqhd,bshd->bhqs", q, k, precision=HI) * DH ** -0.5
    probs = jax.nn.softmax(jnp.where(keep[:, None], logits, -1e30), axis=-1)
    out = jnp.einsum("bhqs,bshd->bqhd", probs, v, precision=HI)
    target = jax.lax.stop_gradient(probs.mean(axis=1))          # [b, q, s]
    log_index = jax.nn.log_softmax(jnp.where(keep, score, -1e30), axis=-1)
    kl = jnp.where(target > 0, target * (
        jnp.log(jnp.where(target > 0, target, 1.0)) - log_index), 0.0)
    return out, kl.sum(axis=-1).mean(), keep.sum(axis=(1, 2))


def _close(got, want, rtol=RTOL):
    scale = float(jnp.abs(want).max())
    return float(jnp.abs(got - want).max()) <= rtol * max(scale, 1e-30)


def _scalar(fn, cot):
    def loss(*args):
        out, index_loss = fn(*args)[:2]
        return (out * cot).sum() + 0.7 * index_loss
    return loss


@pytest.mark.parametrize("s,topk,block,heads,kv_heads,tied", CASES)
def test_op_is_the_dense_masked_computation(s, topk, block, heads, kv_heads,
                                            tied):
    args = _inputs(s, heads, kv_heads, tied)
    op = lambda *a: sparse_attention(*a, topk=topk, block=block)  # noqa: E731
    out, index_loss, selected = op(*args)
    want = _dense(*args, topk)
    assert _close(out, want[0])
    assert abs(float(index_loss) - float(want[1])) <= RTOL * float(want[1])
    # every query attends exactly min(t + 1, topk) keys
    by_hand = sum(min(t + 1, topk) for t in range(s))
    assert selected.dtype == jnp.int32
    assert list(np.asarray(selected)) == [by_hand] * B == list(want[2])
    cot = jnp.asarray(np.random.default_rng(1).standard_normal(
        out.shape).astype(np.float32))
    got_g = jax.grad(_scalar(op, cot), argnums=range(6))(*args)
    want_g = jax.grad(_scalar(lambda *a: _dense(*a, topk), cot),
                      argnums=range(6))(*args)
    for name, got, want_leaf in zip(
            ("q", "k", "v", "index_q", "index_k", "index_w"), got_g, want_g):
        assert _close(got, want_leaf, rtol=3e-5), name
    if s <= topk:       # nothing to select: causal attention
        k, v = (jnp.repeat(a, heads // kv_heads, axis=2)
                for a in args[1:3])
        assert _close(out, full_attention(args[0], k, v, causal=True),
                      rtol=1e-5)


S = 96


def _bits(rng, low, high):
    """Small int32 to read as float32 bit patterns (denormals and +0.0)
    or to add to a float's bits (neighbours an ulp apart)."""
    return rng.integers(low, high, (S, S)).astype(np.int32)


def _indexer_scores(tied):
    score = np.asarray(_scores(*_inputs(S, 4, 2, tied, seed=3)[3:])[0])
    if tied:            # the tie is real: most causal scores are equal
        assert float((score == 0).mean()) > 0.5
    return score


# [S, S] float32 by kind; every query's threshold falls among them
SCORE_KINDS = {
    "indexer": lambda rng: _indexer_scores(False),
    "indexer_tied": lambda rng: _indexer_scores(True),
    "normal": lambda rng: rng.standard_normal((S, S)),
    "negative": lambda rng: -np.abs(rng.standard_normal((S, S))) - 1e-3,
    "quantised": lambda rng: np.round(0.7 * rng.standard_normal((S, S))),
    "equal": lambda rng: np.full((S, S), 0.25),
    "signed_zeros": lambda rng: rng.choice(
        np.float32([0.0, -0.0, 0.0, -0.0, 1.0, -1.0]), (S, S)),
    "ulp_apart": lambda rng: rng.choice(np.float32([1.0, -1.0]), (S, S)) * (
        np.float32(1.0).view(np.int32) + _bits(rng, -2, 3)).view(np.float32),
    "denormal": lambda rng: rng.choice(np.float32([1.0, -1.0]), (S, S))
    * _bits(rng, 0, 6).view(np.float32),
    "large": lambda rng: rng.choice(np.float32(
        [3e38, -3e38, np.finfo(np.float32).max, -np.finfo(np.float32).max,
         1e-38, -1e-38, 1.0, 0.0]), (S, S)) * rng.choice(
             np.float32([1.0, 0.5, 0.25]), (S, S)),
}
SELECT_CASES = [(kind, topk) for kind in SCORE_KINDS
                for topk in (1, S // 4, S - 1)]


def _kind(kind):
    score = np.asarray(SCORE_KINDS[kind](np.random.default_rng(5)),
                       np.float32)
    causal = np.arange(S)[None, :] <= np.arange(S)[:, None]
    return score, causal


@pytest.mark.parametrize("kind,topk", SELECT_CASES)
def test_each_query_selects_exactly_its_topk_earliest_on_ties(kind, topk):
    """``_select`` against the stable argsort, whatever the scores look
    like; queries 0..topk-2 have fewer than ``topk`` causal keys and keep
    them all."""
    score, causal = _kind(kind)
    keep = np.asarray(_select(jnp.asarray(score), jnp.asarray(causal), topk))
    assert (keep.sum(axis=1) == np.minimum(np.arange(S) + 1, topk)).all()
    assert (keep == _mask(score[None], topk)[0]).all()
    assert not keep[~causal].any()


@pytest.mark.parametrize("kind,topk", SELECT_CASES)
def test_the_threshold_is_the_topk_th_value_bit_for_bit(kind, topk):
    """The search returns the number ``lax.top_k`` returns last, in every
    bit; both are read through ``_ordered``, which changes nothing but
    -0.0 into +0.0 (equal floats, one key)."""
    score, causal = _kind(kind)
    masked = jnp.where(causal, score, -jnp.inf)
    want = _ordered(jax.lax.top_k(masked, topk)[0][:, -1:])
    got = _kth_key(_ordered(masked), topk)
    assert got.dtype == jnp.int32 and got.shape == (S, 1)
    assert (np.asarray(got) == np.asarray(want)).all()
    # fewer than topk causal keys: -inf, every causal key above it
    short = np.asarray(got)[:topk - 1, 0]
    assert (short == -0x7f800000).all()


def test_ordered_bits_keep_the_floats_order_and_fold_the_zeros():
    floats = np.float32([-np.inf, -3e38, -1.0, -1e-45, -0.0, 0.0, 1e-45, 1.0,
                         3e38, np.inf])
    keys = np.asarray(_ordered(jnp.asarray(floats))).tolist()
    assert keys[0] == -0x7f800000 and keys[4] == keys[5] == 0
    assert keys[:5] == sorted(set(keys[:5])) and keys[5:] == sorted(
        set(keys[5:]))


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def test_the_backward_pass_does_not_search_again(capsys):
    """In the gradient the search (one pass's compare-and-count per
    candidate digit, the body of its loop) appears once a span of keys
    that selects — the forward body of its ``lax.map`` — not again in the
    recomputation, and what a block saves for it is the [block, 1]
    threshold and room: no [block, keys] mask or scores."""
    s, topk, block = 96, 32, 16
    args = _inputs(s, 4, 2, False)

    def loss(*a):
        out, index_loss, _ = sparse_attention(*a, topk=topk, block=block)
        return (out ** 2).sum() + index_loss

    wide = [e for e in _eqns(jax.make_jaxpr(jax.grad(loss, range(6)))(
        *args).jaxpr) if e.invars and e.invars[0].aval.shape[-2:] in (
            (block, 64), (block, 96))]
    counts = [e for e in wide if e.primitive.name == "ge"]
    assert all(e.invars[0].aval.dtype == jnp.int32 for e in counts)
    spans = 2                                   # keys 0..63 and 0..95
    assert len(counts) == spans * (2 ** _DIGIT - 1)
    assert not [e for e in wide if e.primitive.name in ("sort", "top_k")]
    # the mask is still rebuilt in the backward pass: cumsum twice a span
    assert len([e for e in wide if e.primitive.name == "cumsum"]) == 2 * spans

    jax.ad_checkpoint.print_saved_residuals(loss, *args)
    saved = re.findall(r"^(\w+)\[([\d,]*)\]", capsys.readouterr().out,
                       re.M)
    shapes = [(dtype, tuple(int(n) for n in dims.split(",") if n))
              for dtype, dims in saved]
    assert len(shapes) > 10
    assert [x for x in shapes if x[0] == "i32" and len(x[1]) > 1] == (
        [("i32", (B, spans, block, 1))] * 2 * spans)
    assert not [x for x in shapes if x[0] not in ("i32", "f32")
                or x[1][-2:] in ((block, 64), (block, 96))]


def test_the_two_losses_do_not_reach_each_other_s_inputs():
    """The attention output has no gradient on the indexer's inputs, and
    the indexer's loss none on q, k, v."""
    args = _inputs(96, 4, 2, False)
    op = lambda *a: sparse_attention(*a, topk=32, block=16)  # noqa: E731
    from_out = jax.grad(lambda *a: (op(*a)[0] ** 2).sum(),
                        argnums=range(6))(*args)
    from_index = jax.grad(lambda *a: op(*a)[1], argnums=range(6))(*args)
    for g in from_out[3:] + from_index[:3]:
        assert not np.asarray(g).any()
    for g in from_out[:3] + from_index[3:]:
        assert np.asarray(g).any()


def test_shapes_that_do_not_tile_are_refused():
    args = _inputs(48, 4, 2, False)
    with pytest.raises(ValueError, match="multiple"):
        sparse_attention(*args, topk=16, block=32)
    bad = _inputs(32, 3, 2, False)
    with pytest.raises(ValueError, match="heads"):
        sparse_attention(*bad, topk=16, block=16)


def test_selected_keys_are_published():
    from byteps_tpu.monitor import metrics

    before = metrics._py_counters.get("bps_dsa_selected_keys_total", 0.0)
    stats = {"layer_0": {"attn": {"selected": (np.array([904, 904]),),
                                  "causal": (np.array([2080, 2080]),)}},
             "layer_1": {"attn": {"selected": (np.array([904, 904]),),
                                  "causal": (np.array([2080, 2080]),)}}}
    published = publish_dsa_stats(stats)
    assert published == {"bps_dsa_kept_keys_ratio": 904 / 2080,
                         "bps_dsa_selected_keys_total": 4 * 904.0}
    assert metrics._py_gauges["bps_dsa_kept_keys_ratio"] == 904 / 2080
    assert (metrics._py_counters["bps_dsa_selected_keys_total"]
            == before + 3616)
    assert publish_dsa_stats({}) == {}
    # by hand at the benchmark's size: 43.75% of the causal pairs
    selected = 2048 * 2049 // 2 + 6144 * 2048
    assert (selected, 8192 * 8193 // 2) == (14_681_088, 33_558_528)
    assert round(100 * selected / 33_558_528, 2) == 43.75

"""``parallel/sparse_attention.py`` against a dense-mask computation written
out here (tier-1, CPU, float32, seeded).

The yardstick shares no code with the op: index scores for all (query, key)
pairs at once; the selection by a stable descending argsort of each query's
causal scores, first ``min(t + 1, topk)`` taken — "the highest, ties to the
earlier key" said the plain way, where the op thresholds on ``lax.top_k``'s
last value and counts ties; one masked softmax over all keys; the KL term.
In float32 on the CPU the two differ by the order sums are taken in: a
relative 1e-5 of the largest entry.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.parallel.ring_attention import full_attention
from byteps_tpu.parallel.sparse_attention import (_select, publish_dsa_stats,
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


@pytest.mark.parametrize("tied", (False, True))
def test_each_query_selects_exactly_its_topk_earliest_on_ties(tied):
    s, topk = 96, 32
    args = _inputs(s, 4, 2, tied, seed=3)
    score = _scores(*args[3:])[0]
    if tied:            # the tie is real: most causal scores are equal
        assert float((score == 0).mean()) > 0.5
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    keep = np.asarray(_select(score, causal, topk))
    assert (keep.sum(axis=1) == np.minimum(np.arange(s) + 1, topk)).all()
    assert (keep == _mask(score[None], topk)[0]).all()
    assert not keep[~np.asarray(causal)].any()
    # all-equal scores: the earliest topk keys
    keep = np.asarray(_select(jnp.zeros((s, s)), causal, topk))
    assert keep[-1, :topk].all() and not keep[-1, topk:].any()


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
